"""Event-time edge cases and the windows below the engine: watermark edge
cases (gaps, empty streams, late-data policies, overflow); engine edge
cases bit-exact against the in-order reference (ragged and tiny chunks, a
whole-stream window, a gap, an all-late chunk, continuation across calls);
the per-element TimestampedWindow; and the range-fold primitives (doubling
table, flip sweep, invertible fast path, ordered fold)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import daba_lite, monoids, swag_base
from repro.core.chunked import ChunkedStream
from repro.core.event_time import (
    EventTimeChunkedStream,
    TimestampedWindow,
    flip_range_fold,
    fold_axis0,
    in_order_reference,
    range_fold,
    range_fold_invertible,
)

from event_time_cases import (
    MONOID_CASES,
    assert_tree_close,
    disordered,
    rng,
    scalar_vals,
)


# ---------------------------------------------------------------------------
# Watermark edge cases
# ---------------------------------------------------------------------------


def test_gap_empties_window_completely():
    """A silence longer than the horizon evicts everything (empty window)."""
    m = monoids.sum_monoid(jnp.int32)
    ts = np.asarray([0, 1, 2, 50, 51, 200], np.float32)
    xs = jnp.asarray(np.arange(6, dtype=np.int32).reshape(6, 1) + 1)
    eng = EventTimeChunkedStream(m, 5.0, slack=0.0, chunk=4, capacity=8, buffer=4)
    res = eng.stream(jnp.asarray(ts), xs)
    assert np.asarray(res.ys)[:, 0].tolist() == [1, 3, 6, 4, 9, 6]
    # the terminal flush watermark (+inf) evicts the whole window...
    assert int(eng.window_fold(res.state)[0]) == 0
    # ...while an unflushed stream keeps the live tail
    live = eng.stream(jnp.asarray(ts), xs, flush=False)
    assert int(eng.window_fold(live.state)[0]) == 6


def test_empty_and_single_element_streams():
    m = monoids.sum_monoid(jnp.int32)
    eng = EventTimeChunkedStream(m, 5.0, chunk=4, capacity=8, buffer=4)
    res = eng.stream(jnp.zeros((0,), jnp.float32), jnp.zeros((0, 1), jnp.int32))
    assert res.ts.shape == (0,) and res.ys is None
    res = eng.stream(jnp.asarray([3.0]), jnp.asarray([[7]], jnp.int32))
    assert np.asarray(res.ys).ravel().tolist() == [7]


def test_empty_chunk_with_pending_buffer_refuses_silent_skip():
    """flush=True on an empty chunk cannot drain a pending buffer — the
    engine must say so instead of quietly dropping the pending outputs."""
    m = monoids.sum_monoid(jnp.int32)
    eng = EventTimeChunkedStream(m, 5.0, slack=3.0, chunk=4, capacity=8, buffer=4)
    part = eng.stream(
        jnp.asarray([0.0, 1.0, 2.0]), jnp.ones((3, 1), jnp.int32), flush=False
    )
    with pytest.raises(ValueError, match="pending"):
        eng.stream(
            jnp.zeros((0,), jnp.float32), jnp.zeros((0, 1), jnp.int32),
            state=part.state,
        )
    # the documented path drains it
    st, out = eng.flush(part.state, jnp.zeros((1, 1), jnp.int32))
    assert int(out["mask"].sum()) > 0


def test_all_late_chunk_policies():
    """A chunk arriving entirely below the watermark: drop / side_output
    discard it (flagged), merge folds it into the live window."""
    m = monoids.sum_monoid(jnp.int32)
    ts = np.asarray([10, 11, 12, 13, 1, 2, 3, 4], np.float32)
    xs = jnp.ones((8, 1), jnp.int32)
    for policy in ("drop", "side_output"):
        eng = EventTimeChunkedStream(
            m, 100.0, slack=0.0, chunk=4, capacity=16, buffer=4,
            late_policy=policy,
        )
        res = eng.stream(jnp.asarray(ts), xs, flush=False)
        assert res.n_late == 4 and res.n_dropped == 4
        assert res.late_rows.tolist() == [4, 5, 6, 7]
        assert np.asarray(res.ys).ravel().tolist() == [1, 2, 3, 4]
        assert int(eng.window_fold(res.state)[0]) == 4
    eng = EventTimeChunkedStream(
        m, 100.0, slack=0.0, chunk=4, capacity=16, buffer=4, late_policy="merge"
    )
    res = eng.stream(jnp.asarray(ts), xs, flush=False)
    assert res.n_late == 4 and res.n_dropped == 0
    assert int(eng.window_fold(res.state)[0]) == 8  # merged into the window


def test_merge_policy_drops_past_horizon_late_data():
    """Merge policy still drops late data older than the live horizon."""
    m = monoids.sum_monoid(jnp.int32)
    ts = np.asarray([100, 101, 102, 103, 1, 99, 102.5, 60], np.float32)
    xs = jnp.ones((8, 1), jnp.int32)
    eng = EventTimeChunkedStream(
        m, 10.0, slack=0.0, chunk=4, capacity=16, buffer=4, late_policy="merge"
    )
    res = eng.stream(jnp.asarray(ts), xs, flush=False)
    # ts=1 and ts=60 are beyond horizon -> dropped; 99, 102.5 merge
    assert res.n_dropped == 2
    assert int(eng.window_fold(res.state)[0]) == 6


def test_buffer_overflow_raises():
    m = monoids.sum_monoid(jnp.int32)
    eng = EventTimeChunkedStream(m, 10.0, slack=1000.0, chunk=4, capacity=8, buffer=2)
    with pytest.raises(RuntimeError, match="overflow"):
        eng.stream(
            jnp.asarray(np.arange(12, dtype=np.float32)),
            jnp.ones((12, 1), jnp.int32),
        )


def test_window_capacity_overflow_raises():
    m = monoids.sum_monoid(jnp.int32)
    eng = EventTimeChunkedStream(m, 1000.0, slack=0.0, chunk=8, capacity=4, buffer=8)
    with pytest.raises(RuntimeError, match="overflow"):
        eng.stream(
            jnp.asarray(np.arange(16, dtype=np.float32)),
            jnp.ones((16, 1), jnp.int32),
        )


# ---------------------------------------------------------------------------
# Engine edge cases: chunk splits, a whole-stream window, a gap, an
# all-late chunk, continuation across calls
# ---------------------------------------------------------------------------


def test_engine_ragged_chunks_and_tiny_chunk():
    """Chunk sizes that straddle T unevenly (C ∤ T, C=1) stay exact."""
    m, mk, _ = MONOID_CASES["affine_i32"]
    T, B = 41, 1
    ats, order = disordered(T, 0.5, 7.0, seed=3)
    axs = jax.tree.map(lambda a: a[order], mk((T, B)))
    ref_ts, ref_ys = in_order_reference(m, ats, axs, 11.0)
    for C in (1, 5, 64):
        eng = EventTimeChunkedStream(
            m, 11.0, slack=7.0, chunk=C, capacity=64, buffer=32
        )
        res = eng.stream(jnp.asarray(ats), axs)
        assert np.array_equal(res.ts, ref_ts), C
        assert_tree_close(res.ys, ref_ys, exact=True, ctx=C)


def test_engine_gap_restart_and_giant_window_bit_exact():
    """Flip-sweep edge cases at engine level, non-commutative monoid:
    a horizon covering the whole stream (single giant segment — every
    released window starts at merge position 0) and a mid-stream time gap
    far beyond the horizon (bulk-evicts the ENTIRE window, restarting from
    empty), both bit-exact vs the in-order reference."""
    m, mk, _ = MONOID_CASES["affine_i32"]
    T, B = 48, 2
    ts = np.sort(rng.uniform(0, 20.0, T)).astype(np.float32)
    ts[T // 2:] += 500.0  # gap ≫ any horizon below: empty-window restart
    xs = mk((T, B))
    for horizon in (1e6, 7.0):  # giant window; ordinary window across the gap
        eng = EventTimeChunkedStream(
            m, horizon, slack=0.0, chunk=16, capacity=128, buffer=16
        )
        res = eng.stream(jnp.asarray(ts), xs)
        ref_ts, ref_ys = in_order_reference(m, ts, xs, horizon)
        assert np.array_equal(res.ts, ref_ts)
        assert_tree_close(res.ys, ref_ys, exact=True, ctx=horizon)


def test_engine_all_late_chunk_bit_exact():
    """A chunk arriving entirely below the watermark (all-late) is dropped
    and counted without disturbing on-time outputs."""
    m, mk, _ = MONOID_CASES["affine_i32"]
    T, B, C = 32, 1, 8
    ts = np.sort(rng.uniform(0, 60.0, T)).astype(np.float32)
    xs = mk((T, B))
    # splice one whole chunk of ancient events into the middle of the stream
    late_ts = np.full(C, -100.0, np.float32)
    ats = np.concatenate([ts[:16], late_ts, ts[16:]])
    axs = jax.tree.map(
        lambda a: jnp.concatenate([a[:16], jnp.zeros((C,) + a.shape[1:],
                                                     a.dtype), a[16:]]), xs
    )
    eng = EventTimeChunkedStream(m, 9.0, slack=0.0, chunk=C, capacity=64,
                                 buffer=16)
    res = eng.stream(jnp.asarray(ats), axs)
    assert int(res.n_late) == C and int(res.n_dropped) == C
    ref_ts, ref_ys = in_order_reference(m, ts, xs, 9.0)
    assert np.array_equal(res.ts, ref_ts)
    assert_tree_close(res.ys, ref_ys, exact=True)


def test_stream_continuation_across_calls():
    """stream(state=...) continues a live event-time window."""
    m = monoids.sum_monoid(jnp.int32)
    ts = np.sort(rng.uniform(0, 50, 40)).astype(np.float32)
    xs = scalar_vals((40, 1), jnp.int32)
    eng = EventTimeChunkedStream(m, 9.0, slack=0.0, chunk=8, capacity=32, buffer=8)
    full = eng.stream(jnp.asarray(ts), xs)
    st = eng.init_state(1)
    first = eng.stream(jnp.asarray(ts[:25]), xs[:25], state=st, flush=False)
    second = eng.stream(jnp.asarray(ts[25:]), xs[25:], state=first.state)
    got = np.concatenate([np.asarray(first.ys), np.asarray(second.ys)])
    assert np.array_equal(got, np.asarray(full.ys))


def test_chunked_stream_timestamped_factory():
    eng = ChunkedStream.timestamped(monoids.sum_monoid(), 5.0, chunk=8)
    assert isinstance(eng, EventTimeChunkedStream)
    res = eng.stream(
        jnp.asarray([0.0, 1.0, 2.0]), jnp.ones((3, 1), jnp.float32)
    )
    assert np.asarray(res.ys).ravel().tolist() == [1.0, 2.0, 3.0]


# ---------------------------------------------------------------------------
# Per-element protocol + primitives
# ---------------------------------------------------------------------------


def test_timestamped_window_matches_reference():
    m, mk, _ = MONOID_CASES["affine_i32"]
    T = 40
    ts = np.sort(rng.uniform(0, 80, T)).astype(np.float32)
    xs = mk((T, 1))
    ref_ts, ref_ys = in_order_reference(m, ts, xs, 13.0)
    win = TimestampedWindow(daba_lite, m, horizon=13.0, capacity=64)
    for i in range(T):
        win.insert(float(ts[i]), jax.tree.map(lambda a: a[i, 0], xs))
        assert_tree_close(
            win.query(), jax.tree.map(lambda a: a[i, 0], ref_ys), exact=True, ctx=i
        )


def test_timestamped_window_watermark_bulk_evict_and_order_check():
    m = monoids.sum_monoid(jnp.int32)
    win = TimestampedWindow(daba_lite, m, horizon=5.0, capacity=32)
    for t in range(8):
        win.insert(float(t), 1)
    assert win.size() == 5  # (7-5, 7] keeps ts 3..7
    evicted = win.advance(100.0)  # watermark jump: ONE bulk evict of the rest
    assert evicted == 5 and win.size() == 0
    assert int(m.lower(win.query())) == 0
    with pytest.raises(ValueError, match="event-time order"):
        win.insert(50.0, 1)  # below the 100.0 watermark path max


def test_range_fold_matches_naive():
    m = monoids.affine_int_monoid()
    M, Q = 23, 17
    arr = jax.vmap(m.lift)(
        (jnp.asarray(rng.integers(-4, 4, M), jnp.int32),
         jnp.asarray(rng.integers(-4, 4, M), jnp.int32))
    )
    starts = jnp.asarray(rng.integers(0, M, Q), jnp.int32)
    ends = jnp.asarray(
        np.minimum(np.asarray(starts) + rng.integers(-1, 9, Q), M - 1), jnp.int32
    )
    got = range_fold(m, arr, starts, ends)
    for q in range(Q):
        acc = m.identity()
        for i in range(int(starts[q]), int(ends[q]) + 1):
            acc = m.combine(acc, swag_base.tree_index(arr, i))
        assert_tree_close(swag_base.tree_index(got, q), acc, exact=True, ctx=q)


def _flip_queries(M, layout, r):
    """Monotone query sets satisfying the flip invariant: ``ends`` strictly
    increasing, ``starts`` non-decreasing (module docstring)."""
    if layout == "singleton":  # every element its own single-entry window
        ends = np.arange(M, dtype=np.int32)
        return ends.copy(), ends
    if layout == "giant":  # one giant segment: every query starts at 0
        ends = np.sort(r.choice(M, size=min(M, 13), replace=False))
        return np.zeros_like(ends, np.int32), ends.astype(np.int32)
    if layout == "empty":  # every span empty → identity rows
        ends = np.sort(r.choice(M, size=min(M, 11), replace=False))
        return (ends + 1).astype(np.int32), ends.astype(np.int32)
    # random widths; max-accumulate keeps starts monotone (and ≤ ends,
    # since each ends[q'] - w[q'] ≤ ends[q'] ≤ ends[q])
    ends = np.sort(r.choice(M, size=min(M, 17), replace=False))
    starts = np.maximum.accumulate(ends - r.integers(0, M, ends.shape[0]))
    return np.clip(starts, 0, None).astype(np.int32), ends.astype(np.int32)


@pytest.mark.parametrize("mname", ["affine_i32", "m4_int", "argmax"])
@pytest.mark.parametrize("layout", ["random", "giant", "singleton", "empty"])
def test_flip_range_fold_matches_retired_table_and_naive(mname, layout):
    """The constant-combine flip sweep ≡ the retired doubling table ≡ the
    per-element loop, bit-exactly, on flip-invariant query sets — including
    non-commutative monoids, a single giant segment, every-element-its-own-
    window, and empty spans."""
    m, mk, _ = MONOID_CASES[mname]
    r = np.random.default_rng(sum(map(ord, mname + layout)))
    M = 29
    arr = jax.vmap(m.lift)(mk((M,)))
    starts, ends = _flip_queries(M, layout, r)
    got = flip_range_fold(m, arr, starts, ends)
    table = range_fold(m, arr, starts, ends)
    assert_tree_close(got, table, exact=True, ctx=(mname, layout))
    for q in range(len(ends)):
        acc = m.identity()
        for i in range(int(starts[q]), int(ends[q]) + 1):
            acc = m.combine(acc, swag_base.tree_index(arr, i))
        assert_tree_close(
            swag_base.tree_index(got, q), acc, exact=True,
            ctx=(mname, layout, q),
        )


def test_range_fold_invertible_matches_generic():
    m = monoids.sum_monoid(jnp.int32)
    M, Q = 19, 11
    arr = jax.vmap(m.lift)(jnp.asarray(rng.integers(-9, 9, M), jnp.int32))
    starts = jnp.asarray(rng.integers(0, M, Q), jnp.int32)
    ends = jnp.asarray(
        np.minimum(np.asarray(starts) + rng.integers(-1, 7, Q), M - 1), jnp.int32
    )
    a = range_fold(m, arr, starts, ends)
    b = range_fold_invertible(m, arr, starts, ends)
    assert_tree_close(a, b, exact=True)


def test_fold_axis0_ordered():
    m = monoids.affine_int_monoid()
    vals = (jnp.asarray(rng.integers(-4, 4, 9), jnp.int32),
            jnp.asarray(rng.integers(-4, 4, 9), jnp.int32))
    lifted = jax.vmap(m.lift)(vals)
    acc = m.identity()
    for i in range(9):
        acc = m.combine(acc, swag_base.tree_index(lifted, i))
    assert_tree_close(fold_axis0(m, lifted), acc, exact=True)
