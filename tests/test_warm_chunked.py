"""Warm-state carry protocol: each algorithm's state ≡ its carry.

Covers:
  * ``state_to_carry`` specializations vs the generic evict/query oracle,
    for every algorithm × int/float/pytree/non-commutative monoids;
  * carry → state → carry round trips (exact for integer monoids) and live
    continuation of reconstructed states, FlatFIT included;
  * ``state_from_chunk`` (the vectorized final-state rebuild) vs bulk insert.

``BatchedSWAG.stream``'s warm routing is test_warm_stream.py.
"""

import pytest

from repro.core import ALGORITHMS, swag_base

from warm_cases import MONOID_CASES, assert_tree_close


def _warm_single(algo, m, mk, n_ops, window, cap=64):
    """A live single-lane state after n_ops slides, plus the values seen."""
    vals = mk((n_ops,)) if n_ops else mk((1,))
    st = algo.init(m, cap)
    for i in range(n_ops):
        st = algo.insert(m, st, swag_base.tree_index(vals, i))
        if int(algo.size(st)) > window:
            st = algo.evict(m, st)
    return st, vals


# ---------------------------------------------------------------------------
# state_to_carry: specialization vs generic oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mname", sorted(MONOID_CASES))
@pytest.mark.parametrize("algo_name", sorted(ALGORITHMS))
def test_state_to_carry_matches_generic_oracle(algo_name, mname):
    m, mk, exact = MONOID_CASES[mname]
    if algo_name == "soe" and not m.invertible:
        pytest.skip("subtract-on-evict needs an invertible monoid")
    algo = ALGORITHMS[algo_name]
    for n_ops, window in [(0, 8), (3, 8), (8, 8), (25, 8), (13, 4), (5, 16)]:
        st, _ = _warm_single(algo, m, mk, n_ops, window)
        carry_s = algo.state_to_carry(m, st, window)
        carry_g = swag_base.generic_state_to_carry(algo, m, st, window)
        assert_tree_close(carry_s, carry_g, exact, (algo_name, mname, n_ops, window))


# ---------------------------------------------------------------------------
# carry_to_state: round trip + live continuation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mname", sorted(MONOID_CASES))
@pytest.mark.parametrize("algo_name", sorted(ALGORITHMS))
def test_carry_round_trip_and_continuation(algo_name, mname):
    m, mk, exact = MONOID_CASES[mname]
    if algo_name == "soe" and not m.invertible:
        pytest.skip("subtract-on-evict needs an invertible monoid")
    algo = ALGORITHMS[algo_name]
    window, n_ops = 8, 20
    st, vals = _warm_single(algo, m, mk, n_ops, window)
    carry = swag_base.state_to_carry(algo, m, st, window)
    if algo_name == "recalc" and not (m.invertible and m.commutative):
        with pytest.raises(NotImplementedError):
            swag_base.carry_to_state(algo, m, carry, 64)
        return
    st2 = swag_base.carry_to_state(algo, m, carry, 64)
    # carry -> state -> carry is exact (same suffix folds)
    carry2 = swag_base.state_to_carry(algo, m, st2, window)
    assert_tree_close(carry, carry2, exact, (algo_name, mname, "roundtrip"))
    # the reconstructed state keeps behaving like a per-element state seeded
    # with the same last window-1 elements the carry represents
    h = window - 1
    ref = algo.init(m, 64)
    for i in range(n_ops - h, n_ops):
        ref = algo.insert(m, ref, swag_base.tree_index(vals, i))
    assert int(algo.size(st2)) == int(algo.size(ref)) == h
    for step in range(h - 1):
        assert_tree_close(
            m.lower(algo.query(m, st2)), m.lower(algo.query(m, ref)),
            exact, (algo_name, mname, "evict", step),
        )
        st2, ref = algo.evict(m, st2), algo.evict(m, ref)
    more = mk((4,))
    for i in range(4):
        v = swag_base.tree_index(more, i)
        st2, ref = algo.insert(m, st2, v), algo.insert(m, ref, v)
        assert_tree_close(
            m.lower(algo.query(m, st2)), m.lower(algo.query(m, ref)),
            exact, (algo_name, mname, "insert", i),
        )


# ---------------------------------------------------------------------------
# FlatFIT (eager, outside ALGORITHMS) conforms to the carry protocol
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mname", sorted(MONOID_CASES))
def test_flatfit_state_to_carry_matches_history_truth(mname):
    """Carry extracted from a (compressed) FlatFIT buffer equals the suffix
    folds computed directly from the value history, and leaves the source
    state untouched (the sweep runs on a copy)."""
    from repro.core import flatfit

    m, mk, exact = MONOID_CASES[mname]
    window = 8
    st = flatfit.init(m, 64)
    hist: list = []
    vals = mk((25,))
    for i in range(25):
        st = flatfit.insert(m, st, swag_base.tree_index(vals, i))
        hist.append(i)
        if flatfit.size(st) > window:
            st = flatfit.evict(m, st)
            hist.pop(0)
        if i % 5 == 0:
            flatfit.query_mut(m, st)  # exercise compressed layouts
    carry = flatfit.state_to_carry(m, st, window)
    assert flatfit.size(st) == window  # extraction must not mutate
    h = window - 1
    for t in range(h):
        acc = m.identity()
        for j in hist[len(hist) - (h - t):]:
            acc = m.combine(acc, m.lift(swag_base.tree_index(vals, j)))
        assert_tree_close(
            swag_base.tree_index(carry, t), acc, exact, (mname, t)
        )


@pytest.mark.parametrize("mname", sorted(MONOID_CASES))
def test_flatfit_carry_round_trip_and_continuation(mname):
    """carry → FlatFIT state (exact compressed-layout specialization, ANY
    monoid) → carry round-trips, and the rebuilt buffer keeps behaving like
    a per-element DABA Lite window seeded with the same elements."""
    from repro.core import flatfit

    m, mk, exact = MONOID_CASES[mname]
    window, n_ops = 8, 20
    st, vals = _warm_single(ALGORITHMS["daba_lite"], m, mk, n_ops, window)
    carry = swag_base.state_to_carry(ALGORITHMS["daba_lite"], m, st, window)
    ff = flatfit.carry_to_state(m, carry, 64)
    carry2 = flatfit.state_to_carry(m, ff, window)
    assert_tree_close(carry, carry2, exact, (mname, "roundtrip"))
    h = window - 1
    ref = ALGORITHMS["daba_lite"].init(m, 64)
    for i in range(n_ops - h, n_ops):
        ref = ALGORITHMS["daba_lite"].insert(m, ref, swag_base.tree_index(vals, i))
    assert flatfit.size(ff) == h
    more = mk((4,))
    for i in range(4):
        v = swag_base.tree_index(more, i)
        ff = flatfit.insert(m, ff, v)
        ref = ALGORITHMS["daba_lite"].insert(m, ref, v)
        ff = flatfit.evict(m, ff)
        ref = ALGORITHMS["daba_lite"].evict(m, ref)
        assert_tree_close(
            m.lower(flatfit.query(m, ff)),
            m.lower(ALGORITHMS["daba_lite"].query(m, ref)),
            exact, (mname, "continue", i),
        )


def test_flatfit_state_from_chunk_dispatcher():
    """The swag_base dispatcher reaches FlatFIT through carry_to_state: one
    suffix scan laid out as a compressed buffer ≡ bulk insert."""
    from repro.core import flatfit

    m, mk, exact = MONOID_CASES["affine_i32"]
    vals = mk((7,))
    st = swag_base.state_from_chunk(flatfit, m, vals, 32)
    ref = flatfit.insert_bulk(m, flatfit.init(m, 32), vals)
    assert flatfit.size(st) == flatfit.size(ref) == 7
    for _ in range(7):
        assert_tree_close(
            m.lower(flatfit.query(m, st)), m.lower(flatfit.query(m, ref)),
            exact, "state_from_chunk",
        )
        st, ref = flatfit.evict(m, st), flatfit.evict(m, ref)


# ---------------------------------------------------------------------------
# state_from_chunk: vectorized rebuild ≡ bulk insert into fresh state
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mname", sorted(MONOID_CASES))
@pytest.mark.parametrize("algo_name", sorted(ALGORITHMS))
def test_state_from_chunk_matches_bulk_insert(algo_name, mname):
    m, mk, exact = MONOID_CASES[mname]
    if algo_name == "soe" and not m.invertible:
        pytest.skip("subtract-on-evict needs an invertible monoid")
    algo = ALGORITHMS[algo_name]
    for k in (1, 7, 12):
        vals = mk((k,))
        st = swag_base.state_from_chunk(algo, m, vals, 32)
        ref = swag_base.insert_bulk(algo, m, algo.init(m, 32), vals)
        assert int(algo.size(st)) == int(algo.size(ref)) == k
        for step in range(k):
            assert_tree_close(
                m.lower(algo.query(m, st)), m.lower(algo.query(m, ref)),
                exact, (algo_name, mname, k, step),
            )
            st, ref = algo.evict(m, st), algo.evict(m, ref)
