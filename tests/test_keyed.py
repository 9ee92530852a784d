"""Keyed window store: per-key windows ≡ per-key per-element scans.

``KeyedChunkedStream`` outputs are bit-exact against a dict-of-single-
windows per-element reference, for integer AND non-commutative monoids
(``affine_i32``, ``m4``), across chunk splits, ragged chunks, flip-sweep
edge layouts and warm continuation; the admission fast path and the
``seg_scan`` kernel path change no output.  The hypothesis sweeps are in
test_keyed_property.py, event-time ``horizon=`` windows in
test_keyed_horizon.py, the directory and carry protocol in
test_keyed_store.py, and the sharded store in test_keyed_sharded.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import monoids
from repro.core.keyed import (
    KeyedChunkedStream,
    KeyedWindowStore,
    seg_suffix_scan,
)
from repro.core.reference import per_key_reference
from repro.obs import counters as obs_counters

from keyed_cases import (
    MONOID_CASES,
    affine_vals,
    rng,
    scalar_vals,
    tree_equal,
    val_list,
)


# ---------------------------------------------------------------------------
# Equivalence vs the per-element reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(MONOID_CASES))
@pytest.mark.parametrize("window,chunk", [(1, 16), (5, 16), (8, 64), (16, 8)])
def test_keyed_stream_matches_reference(name, window, chunk):
    make, gen = MONOID_CASES[name]
    m = make()
    T, U = 200, 13
    keys = rng.integers(0, U, T).astype(np.int32)
    vals = gen(T)
    eng = KeyedChunkedStream(m, window, slots=U + 3, chunk=chunk)
    _, ys = eng.stream(keys, vals)
    ref = per_key_reference(m, keys, val_list(vals), window)
    assert tree_equal(ys, ref)


@pytest.mark.parametrize("name", ["affine_i32", "m4"])
@pytest.mark.parametrize("layout", ["giant", "fresh_keys"])
def test_keyed_flip_sweep_edge_layouts(name, layout):
    """Flip-sweep edge cases through the full bulk path, non-commutative
    monoids, ragged final chunk: a single giant segment (every row one key)
    and every-row-a-new-key (C singleton segments per chunk), for both
    the W ≤ C (suffix+prefix) and W > C (prefix-only) sweep regimes."""
    make, gen = MONOID_CASES[name]
    m = make()
    T = 90  # chunk=32 → ragged 26-row final chunk
    if layout == "giant":
        keys = np.zeros(T, dtype=np.int32)
        slots = 4
    else:
        keys = np.arange(T, dtype=np.int32)
        slots = T + 2
    vals = gen(T)
    for window in (4, 48):
        eng = KeyedChunkedStream(m, window, slots=slots, chunk=32)
        _, ys = eng.stream(keys, vals)
        ref = per_key_reference(m, keys, val_list(vals), window)
        assert tree_equal(ys, ref), (name, layout, window)


def test_keyed_combines_per_element_flat_in_window():
    """The constant-combine claim, measured at runtime: sweep ⊗-invocations
    per chunk row do not grow with the window (the retired range-fold table
    added a log2(W) doubling-table factor).  Counts may DROP once W > C
    (the suffix half of the flip sweep is statically elided)."""
    C, K, rounds = 64, 16, 3
    m = monoids.max_monoid(jnp.int32)  # non-invertible → flip-sweep path
    keys = jnp.asarray(rng.integers(0, K, C), jnp.int32)
    xs = scalar_vals(C)
    per_row = {}
    for W in (8, 64, 512):
        store = KeyedWindowStore(m, W, slots=K, instrument_combines=True)
        state = store.init_state()
        state, _, _ = store.update_chunk(state, keys, xs)  # admit + warm
        obs_counters.combines.reset()
        for _ in range(rounds):
            state, _, _ = store.update_chunk(state, keys, xs)
        # read() runs jax.effects_barrier() before snapshotting
        per_row[W] = obs_counters.combines.read()["keyed"] / (rounds * C)
    assert per_row[8] > 0, per_row  # the instrumentation actually fired
    assert per_row[64] <= 1.25 * per_row[8], per_row
    assert per_row[512] <= 1.25 * per_row[8], per_row


@pytest.mark.parametrize("name", ["sum_i32", "affine_i32"])
def test_keyed_warm_continuation(name):
    """Carries persist across stream() calls: two halves ≡ one stream."""
    make, gen = MONOID_CASES[name]
    m = make()
    T, U, W = 160, 7, 6
    keys = rng.integers(0, U, T).astype(np.int32)
    vals = gen(T)
    eng = KeyedChunkedStream(m, W, slots=U, chunk=32)
    st, y1 = eng.stream(keys[:90], jax.tree.map(lambda a: a[:90], vals))
    st, y2 = eng.stream(
        keys[90:], jax.tree.map(lambda a: a[90:], vals), state=st
    )
    both = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), y1, y2)
    ref = per_key_reference(m, keys, val_list(vals), W)
    assert tree_equal(both, ref)


def test_keyed_masked_rows_ignored():
    m = monoids.sum_monoid(jnp.int32)
    eng = KeyedChunkedStream(m, 4, slots=8, chunk=8)
    keys = jnp.asarray([1, 2, 1, 2, 1, 2, 1, 2], jnp.int32)
    xs = jnp.arange(8, dtype=jnp.int32)
    mask = jnp.asarray([True, True, True, True, False, False, False, False])
    st, ys, info = eng.process_chunk(eng.init_state(), keys, xs, None, mask)
    ref = per_key_reference(m, [1, 2, 1, 2], [0, 1, 2, 3], 4)
    assert jnp.array_equal(ys[:4], ref)
    agg, found = eng.query(st, jnp.asarray([1, 2], jnp.int32))
    assert int(agg[0]) == 2 and int(agg[1]) == 4  # masked rows never folded
    assert int(st["n_seen"].sum()) == 4


def test_keyed_query_unknown_key_identity():
    m = monoids.sum_monoid(jnp.int32)
    eng = KeyedChunkedStream(m, 4, slots=4, chunk=4)
    st, _ = eng.stream(np.asarray([5], np.int32), jnp.asarray([7], jnp.int32))
    agg, found = eng.query(st, jnp.asarray([5, 6], jnp.int32))
    assert bool(found[0]) and not bool(found[1])
    assert int(agg[0]) == 7 and int(agg[1]) == 0


# ---------------------------------------------------------------------------
# Admission fast path + seg-scan kernel dispatch
# ---------------------------------------------------------------------------


def test_admission_fast_path_taken_and_bit_exact():
    """Steady-state chunks with NO new keys must take the all-hit fast
    branch (no sequential admission work), counted via the trace-side
    instrumentation callback — and stay bit-exact vs the reference."""
    m = monoids.sum_monoid(jnp.int32)
    W, chunk, U = 5, 16, 8
    # chunk 0 contains the whole key universe (admits everything in one
    # slow-path pass); the following 6 chunks reuse only known keys
    warm = np.concatenate([np.arange(U), rng.integers(0, U, chunk - U)])
    warm = warm.astype(np.int32)
    keys = rng.integers(0, U, 6 * chunk).astype(np.int32)
    wvals, vals = scalar_vals(chunk), scalar_vals(6 * chunk)
    eng = KeyedChunkedStream(m, W, slots=U + 2, chunk=chunk,
                             instrument_admission=True)
    obs_counters.admission.reset()
    st, y0 = eng.stream(warm, wvals)
    st, ys = eng.stream(keys, vals, state=st)
    # read() flushes the debug callbacks (effects_barrier) before snapshotting
    counts = obs_counters.admission.read()
    assert counts["slow"] == 1, counts  # admitting chunk
    assert counts["fast"] == 6, counts  # steady state
    # the legacy module-level alias must stay the same live group
    from repro.core.keyed import ADMISSION_COUNTS

    assert ADMISSION_COUNTS is obs_counters.admission
    assert ADMISSION_COUNTS["fast"] == 6  # dict-compat read on the alias
    # the fast path must not change results: bit-exact vs the reference
    ref = per_key_reference(
        m, np.concatenate([warm, keys]),
        val_list(jnp.concatenate([wvals, vals])), W,
    )
    got = jnp.concatenate([y0, ys])
    assert tree_equal(got, ref)


def test_store_seg_kernel_matches_lax_path():
    """use_seg_kernel=True (Pallas segmented suffix scan, interpret mode on
    CPU) reproduces the default lax path bit-exactly at the store level."""
    m = monoids.sum_monoid(jnp.int32)
    W, chunk, U, T = 6, 32, 11, 300
    keys = rng.integers(0, U, T).astype(np.int32)
    vals = scalar_vals(T)
    base = KeyedChunkedStream(m, W, slots=U + 1, chunk=chunk)
    kern = KeyedChunkedStream(m, W, slots=U + 1, chunk=chunk,
                              use_seg_kernel=True)
    _, y0 = base.stream(keys, vals)
    _, y1 = kern.stream(keys, vals)
    assert jnp.array_equal(y0, y1)
    ref = per_key_reference(m, keys, val_list(vals), W)
    assert tree_equal(y1, ref)
    # a pytree monoid has no scalar op → explicit kernel request is an error
    with pytest.raises(ValueError):
        KeyedWindowStore(monoids.affine_int_monoid(), W, slots=4,
                         use_seg_kernel=True)._seg_scan(
            jnp.zeros(4, bool), (jnp.zeros(4, jnp.int32),) * 2)


def test_seg_suffix_scan_non_commutative():
    m = monoids.affine_int_monoid()
    vals = affine_vals(9)
    lifted = jax.vmap(m.lift)(vals)
    ends = jnp.asarray([False, False, True, False, True, False, False, False, True])
    out = seg_suffix_scan(m, ends, lifted)
    segs = [(0, 2), (3, 4), (5, 8)]
    for a, b in segs:
        for i in range(a, b + 1):
            acc = jax.tree.map(lambda l: l[i], lifted)
            for j in range(i + 1, b + 1):
                acc = m.combine(acc, jax.tree.map(lambda l: l[j], lifted))
            got = jax.tree.map(lambda l: l[i], out)
            assert tree_equal(got, acc)
