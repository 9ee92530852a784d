"""Cases shared by the warm-carry test modules: the monoids under test
(commutative and not, invertible and not, scalar and pytree) with their
value generators, the exact / allclose tree comparison, and the warm
routing check that test_warm_stream.py and test_warm_stream_baselines.py
run for their algorithms."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import GENERAL_ALGORITHMS, monoids
from repro.core.batched import BatchedSWAG

rng = np.random.default_rng(1)


def scalar_vals(shape, dtype=jnp.float32):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(-9, 9, shape), dtype)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def affine_vals(shape, dtype=jnp.int32):
    return (
        jnp.asarray(rng.integers(-5, 5, shape), dtype),
        jnp.asarray(rng.integers(-5, 5, shape), dtype),
    )


# Spans the algebraic classes: commutative+invertible scalar (kernel path,
# exact int), commutative invertible pytree, and two NON-commutative
# NON-invertible monoids (one exact-integer, one float).
MONOID_CASES = {
    "sum_i32": (monoids.sum_monoid(jnp.int32),
                lambda s: scalar_vals(s, jnp.int32), True),
    "mean": (monoids.mean_monoid(), scalar_vals, False),
    "affine_i32": (monoids.affine_int_monoid(), affine_vals, True),
    "m4": (monoids.m4_monoid(), scalar_vals, False),
}


def assert_tree_close(a, b, exact, ctx=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if exact:
            assert np.array_equal(x, y), (ctx, x, y)
        else:
            assert np.allclose(x, y, rtol=1e-4, atol=1e-4), (ctx, x, y)


def _warm_state(b, mk, B, window, n_warm):
    st = b.init(B)
    if n_warm:
        st, _ = b.stream(st, mk((n_warm, B)), window, chunked=False)
    return st


def check_warm_stream_matches_per_element(algo_name, mname):
    """``BatchedSWAG.stream`` from live windows: chunked ≡ per-element
    outputs and final queries across ragged splits, and the rebuilt final
    state keeps behaving."""
    m, mk, exact = MONOID_CASES[mname]
    algo = GENERAL_ALGORITHMS[algo_name]
    window, B = 8, 3
    # one BatchedSWAG for every split: it holds no stream state, only its
    # compiled programs, which the splits then share
    b = BatchedSWAG(algo, m, 12)
    for n_warm, T, chunk in [(0, 37, 16), (3, 37, 16), (11, 41, 13), (8, 20, 4)]:
        st = _warm_state(b, mk, B, window, n_warm)
        xs = mk((T, B))
        st_pe, ys_pe = b.stream(st, xs, window, chunked=False)
        st_ch, ys_ch = b.stream(st, xs, window, chunked=True, chunk=chunk)
        ctx = (algo_name, mname, n_warm, T, chunk)
        assert_tree_close(ys_ch, ys_pe, exact, ctx)
        assert_tree_close(b.query(st_ch), b.query(st_pe), exact, ctx)
        # the rebuilt final state keeps behaving
        more = mk((B,))
        st_pe, st_ch = b.insert(st_pe, more), b.insert(st_ch, more)
        st_pe, st_ch = b.evict(st_pe), b.evict(st_ch)
        assert_tree_close(b.query(st_ch), b.query(st_pe), exact, ctx)
