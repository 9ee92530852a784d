"""Cases shared by the keyed test modules: the monoids under test with
their value generators, and the bit-exact tree comparison the per-key
oracles (``repro.core.reference``) are checked with."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import monoids

rng = np.random.default_rng(0)


def tree_equal(a, b):
    return all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def scalar_vals(n, dtype=jnp.int32):
    return jnp.asarray(rng.integers(-9, 9, n), dtype)


def affine_vals(n):
    return (
        jnp.asarray(rng.integers(-4, 4, n), jnp.int32),
        jnp.asarray(rng.integers(-5, 5, n), jnp.int32),
    )


MONOID_CASES = {
    "sum_i32": (lambda: monoids.sum_monoid(jnp.int32), scalar_vals),
    "max_i32": (lambda: monoids.max_monoid(jnp.int32), scalar_vals),
    "affine_i32": (lambda: monoids.affine_int_monoid(), affine_vals),
    "m4": (lambda: monoids.m4_monoid(), lambda n: scalar_vals(n, jnp.float32)),
}


def val_list(vals):
    leaves = [np.asarray(l) for l in jax.tree.leaves(vals)]
    if isinstance(vals, tuple):
        return [tuple(int(l[i]) for l in leaves) for i in range(len(leaves[0]))]
    return list(leaves[0])
