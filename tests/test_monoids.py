"""Monoid laws (associativity, identity) — hypothesis property tests."""

import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, don't break collection
from hypothesis import given, settings, strategies as st

from repro.core import monoids

INT_VALS = st.integers(min_value=-1000, max_value=1000)


def tree_close(a, b, tol=1e-4):
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(
        np.allclose(np.asarray(x), np.asarray(y), rtol=tol, atol=tol)
        for x, y in zip(la, lb)
    )


CASES = [
    ("sum_i32", monoids.sum_monoid(jnp.int32), INT_VALS, True),
    ("max_i32", monoids.max_monoid(jnp.int32), INT_VALS, True),
    ("min_i32", monoids.min_monoid(jnp.int32), INT_VALS, True),
    ("maxcount", monoids.maxcount_monoid(jnp.float32),
     st.integers(0, 10).map(float), True),
    ("argmax", monoids.argmax_monoid(),
     st.tuples(st.integers(0, 10).map(float), st.integers(0, 100)), True),
    ("m4", monoids.m4_monoid(), st.integers(-50, 50).map(float), True),
    ("affine_i32", monoids.affine_int_monoid(),
     st.tuples(INT_VALS, INT_VALS), True),
    ("bloom", monoids.bloom_monoid(8), st.integers(0, 10_000), True),
    ("countmin", monoids.countmin_monoid(2, 16), st.integers(0, 10_000), True),
    ("hll", monoids.hll_monoid(16), st.integers(0, 10_000), True),
    # kll: with 3 lifted singletons no compaction triggers, so the merge is
    # a plain sorted union — associative and commutative bit-exactly
    ("kll", monoids.kll_monoid(k=32, levels=4),
     st.integers(-100, 100).map(float), True),
    # topk: with 3 lifted singletons no truncation triggers, and the
    # canonical (count desc, key asc) re-sort makes the merge bit-exact
    ("topk", monoids.topk_monoid(8), st.integers(0, 1000), True),
    ("mean", monoids.mean_monoid(), st.integers(-100, 100).map(float), False),
    ("geomean", monoids.geomean_monoid(),
     st.integers(1, 100).map(float), False),
    ("variance", monoids.variance_monoid(),
     st.integers(-20, 20).map(float), False),
    ("logsumexp", monoids.logsumexp_monoid(),
     st.integers(-20, 20).map(float), False),
]


@pytest.mark.parametrize("name,m,strat,exact", CASES, ids=[c[0] for c in CASES])
@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_associativity(name, m, strat, exact, data):
    a = m.lift(data.draw(strat))
    b = m.lift(data.draw(strat))
    c = m.lift(data.draw(strat))
    left = m.combine(m.combine(a, b), c)
    right = m.combine(a, m.combine(b, c))
    if exact:
        import jax

        assert all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(left), jax.tree.leaves(right))
        )
    else:
        assert tree_close(left, right)


@pytest.mark.parametrize("name,m,strat,exact", CASES, ids=[c[0] for c in CASES])
@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_identity(name, m, strat, exact, data):
    a = m.lift(data.draw(strat))
    assert tree_close(m.combine(m.identity(), a), a, tol=1e-6)
    assert tree_close(m.combine(a, m.identity()), a, tol=1e-6)


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_left_inverse(data):
    """inverse_front(lift(e) ⊗ r, lift(e)) == r for invertible monoids."""
    for m, strat in [
        (monoids.sum_monoid(jnp.int32), INT_VALS),
        (monoids.mean_monoid(), st.integers(-100, 100).map(float)),
        (monoids.countmin_monoid(2, 16), st.integers(0, 1000)),
    ]:
        e = m.lift(data.draw(strat))
        r = m.lift(data.draw(strat))
        combined = m.combine(e, r)
        recovered = m.inverse_front(combined, e)
        assert tree_close(recovered, r, tol=1e-5)


def test_noncommutative_monoids_are_noncommutative():
    """The monoids we rely on for order-sensitivity really are order-sensitive."""
    m = monoids.affine_int_monoid()
    a, b = m.lift((2, 3)), m.lift((5, 7))
    ab, ba = m.combine(a, b), m.combine(b, a)
    assert int(ab["b"]) != int(ba["b"])

    am = monoids.argmax_monoid()
    x, y = am.lift((1.0, 10)), am.lift((1.0, 20))
    assert int(am.combine(x, y)["i"]) == 10  # tie → older wins
    assert int(am.combine(y, x)["i"]) == 20


def test_bloom_membership():
    m = monoids.bloom_monoid(16)
    filt = m.identity()
    for v in [3, 17, 99]:
        filt = m.combine(filt, m.lift(v))
    for v in [3, 17, 99]:
        assert bool(monoids.bloom_contains(filt, jnp.asarray(v)))
    misses = sum(
        bool(monoids.bloom_contains(filt, jnp.asarray(v))) for v in range(1000, 1100)
    )
    assert misses < 10  # false-positive rate sanity


def test_topk_exact_below_capacity():
    """≤ k distinct keys → exact counts, heaviest first, key tie-break."""
    m = monoids.topk_monoid(4)
    agg = m.identity()
    for v in [1, 2, 1, 3, 1, 2, 1]:
        agg = m.combine(agg, m.lift(v))
    assert monoids.topk_items(agg) == [(1, 4), (2, 2), (3, 1)]


def test_topk_heavy_hitters_survive_truncation():
    """Keys heavier than the dropped tail stay resident past capacity."""
    import jax

    from repro.core.event_time import fold_axis0

    rng = np.random.default_rng(0)
    stream = np.concatenate(
        [np.full(500, 9), np.full(300, 13), rng.integers(100, 200, 400)]
    ).astype(np.int32)
    rng.shuffle(stream)
    m = monoids.topk_monoid(8)
    agg = fold_axis0(m, jax.vmap(m.lift)(jnp.asarray(stream)))
    items = monoids.topk_items(agg)
    assert items[0] == (9, 500)
    assert items[1] == (13, 300)


def test_topk_batched_combine():
    """Leading batch axes broadcast (the seg-scan calling convention)."""
    import jax

    m = monoids.topk_monoid(8)
    a = jax.tree.map(lambda x: jnp.stack([x, x]), m.lift(3))
    b = jax.tree.map(lambda x: jnp.stack([x, x]), m.lift(3))
    out = m.combine(a, b)
    assert out["keys"].shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out["counts"][:, 0]), [2, 2])


def test_hll_estimate_tracks_cardinality():
    import jax

    from repro.core.event_time import fold_axis0

    m = monoids.hll_monoid(64)
    for n in (50, 1000, 10_000):
        agg = fold_axis0(m, jax.vmap(m.lift)(jnp.arange(n, dtype=jnp.int32)))
        est = float(monoids.hll_estimate(agg))
        assert abs(est - n) / n < 0.35, (n, est)


def test_countmin_estimate():
    m = monoids.countmin_monoid(4, 64)
    sk = m.identity()
    for v, n in [(5, 3), (9, 1)]:
        for _ in range(n):
            sk = m.combine(sk, m.lift(v))
    assert int(monoids.countmin_estimate(sk, 5)) >= 3
    assert int(monoids.countmin_estimate(sk, 9)) >= 1


@pytest.mark.parametrize("n", [16, 128])
def test_kll_fold_compiles_in_bounded_time(n):
    """A jitted fold of n sketches compiles in time linear in its combines:
    XLA:CPU once spent minutes from 8 sketches up (strided-slice gathers
    fused across fold rounds, each combine unrolled over its levels)."""
    import time

    import jax

    from repro.core.event_time import fold_axis0

    m = monoids.kll_monoid(k=64, levels=8)
    lifted = jax.vmap(m.lift)(jnp.arange(n, dtype=jnp.float32))
    t0 = time.perf_counter()
    jax.jit(lambda t: fold_axis0(m, t)).lower(lifted).compile()
    assert time.perf_counter() - t0 < 30.0


@pytest.mark.parametrize("n", [0, 1, 32, 33, 1000, 8192])
def test_kll_lift_batch_matches_fold(n):
    """``lift_batch`` (one sort, then compaction up the levels) is the fold
    of the lifted values: bit-exact while one level holds them, and beyond
    that with every item's weight kept and ranks within 2% of n (plus the
    one weight-2 item's share at the first compaction)."""
    import jax

    from repro.core.event_time import fold_axis0

    k, L, N = 32, 10, 8192
    m = monoids.kll_monoid(k=k, levels=L)
    vals = np.random.default_rng(n).permutation(N).astype(np.float32)
    mask = np.arange(N) < n
    agg = jax.jit(m.lift_batch)(jnp.asarray(vals), jnp.asarray(mask))
    items, cnt = np.asarray(agg["items"]), np.asarray(agg["n"])
    assert (np.isfinite(items).sum(-1) == cnt).all()
    assert int((cnt * 2 ** np.arange(L)).sum()) == n  # within capacity
    if n <= k:
        want = fold_axis0(m, jax.vmap(m.lift)(jnp.asarray(vals[:n])))
        for x, y in zip(jax.tree.leaves(agg), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    else:
        qs = (0.1, 0.5, 0.95, 0.99)
        est = np.asarray(monoids.kll_quantiles(agg, qs))
        ranks = [(vals[:n] <= e).mean() for e in est]
        np.testing.assert_allclose(ranks, qs, atol=0.02 + 2 / n)
