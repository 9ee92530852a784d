"""Cases shared by the event-time test modules: the monoids under test
with their value generators, a lateness-bounded stream generator and the
exact / allclose tree comparison."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import monoids

rng = np.random.default_rng(7)


def scalar_vals(shape, dtype=jnp.float32):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(-9, 9, shape), dtype)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def int_float_vals(shape):  # integer-valued floats: m4/argmax stay bit-exact
    return jnp.asarray(rng.integers(-9, 9, shape).astype(np.float32))


def affine_vals(shape):
    return (
        jnp.asarray(rng.integers(-5, 5, shape), jnp.int32),
        jnp.asarray(rng.integers(-5, 5, shape), jnp.int32),
    )


def argmax_vals(shape):
    return (
        int_float_vals(shape),
        jnp.asarray(rng.integers(0, 1000, shape), jnp.int32),
    )


# ≥ 2 NON-commutative monoids verified bit-exactly (affine_i32: exact
# modular arithmetic; m4 + argmax: pure selection on integer-valued floats),
# plus invertible-fast-path and float-allclose coverage.
MONOID_CASES = {
    "sum_i32": (monoids.sum_monoid(jnp.int32),
                lambda s: scalar_vals(s, jnp.int32), True),
    "affine_i32": (monoids.affine_int_monoid(), affine_vals, True),
    "m4_int": (monoids.m4_monoid(), int_float_vals, True),
    "argmax": (monoids.argmax_monoid(), argmax_vals, True),
    "mean": (monoids.mean_monoid(), scalar_vals, False),
}


def assert_tree_close(a, b, exact, ctx=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if exact:
            assert np.array_equal(x, y), (ctx, x, y)
        else:
            assert np.allclose(x, y, rtol=1e-4, atol=1e-4), (ctx, x, y)


def disordered(T, disorder, slack, *, seed, int_ts=False):
    """(arrival_ts, arrival_order): lateness bounded by ``slack``."""
    r = np.random.default_rng(seed)
    if int_ts:
        ts = np.sort(r.integers(0, 3 * T, T)).astype(np.int32)
        delay = (r.random(T) < disorder) * r.integers(0, max(int(slack), 1), T)
    else:
        ts = np.sort(r.uniform(0, 2.0 * T, T)).astype(np.float32)
        delay = (r.random(T) < disorder) * r.uniform(0, slack, T)
    order = np.argsort(ts + delay, kind="stable")
    return ts[order], order
