"""Keyed window-store subsystem: per-key windows ≡ per-key per-element scans.

Covers the keyed tentpole:
  * ``KeyedChunkedStream`` outputs bit-exact vs a dict-of-single-windows
    per-element reference, for integer AND non-commutative monoids
    (``affine_i32``, ``m4``), across chunk splits / ragged chunks / warm
    continuation — plus a hypothesis property sweep;
  * ``KeyDirectory`` collision, LRU-eviction, and TTL-expiry edge cases;
  * window-lane reset on slot reuse (no cross-tenant leakage);
  * SWAG interop: ``export_states`` / ``adopt_states`` through the warm
    carry protocol;
  * ``ShardedKeyedStore``: hash-sharded key space over a 4-device mesh
    reproduces the single-store outputs with zero steady-state collectives
    (subprocess, host platform device count);
  * ``KeyedTelemetry`` per-key metrics + state_dict round trip.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daba_lite, monoids
from repro.core.keyed import (
    KeyDirectory,
    KeyedChunkedStream,
    KeyedWindowStore,
    seg_suffix_scan,
)
from repro.core.reference import per_key_horizon_reference, per_key_reference
from repro.core.telemetry import KeyedTelemetry
from repro.obs import counters as obs_counters

rng = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# Oracle helpers (the per-key oracles live in repro.core.reference)
# ---------------------------------------------------------------------------


def _tree_equal(a, b):
    return all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _scalar_vals(n, dtype=jnp.int32):
    return jnp.asarray(rng.integers(-9, 9, n), dtype)


def _affine_vals(n):
    return (
        jnp.asarray(rng.integers(-4, 4, n), jnp.int32),
        jnp.asarray(rng.integers(-5, 5, n), jnp.int32),
    )


MONOID_CASES = {
    "sum_i32": (lambda: monoids.sum_monoid(jnp.int32), _scalar_vals),
    "max_i32": (lambda: monoids.max_monoid(jnp.int32), _scalar_vals),
    "affine_i32": (lambda: monoids.affine_int_monoid(), _affine_vals),
    "m4": (lambda: monoids.m4_monoid(), lambda n: _scalar_vals(n, jnp.float32)),
}


def _val_list(vals):
    leaves = [np.asarray(l) for l in jax.tree.leaves(vals)]
    if isinstance(vals, tuple):
        return [tuple(int(l[i]) for l in leaves) for i in range(len(leaves[0]))]
    return list(leaves[0])


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
    ref = per_key_reference(m, keys, _val_list(vals), window)
    assert _tree_equal(ys, ref)


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
        ref = per_key_reference(m, keys, _val_list(vals), window)
        assert _tree_equal(ys, ref), (name, layout, window)


def test_keyed_combines_per_element_flat_in_window():
    """The constant-combine claim, measured at runtime: sweep ⊗-invocations
    per chunk row do not grow with the window (the retired range-fold table
    added a log2(W) doubling-table factor).  Counts may DROP once W > C
    (the suffix half of the flip sweep is statically elided)."""
    C, K, rounds = 64, 16, 3
    m = monoids.max_monoid(jnp.int32)  # non-invertible → flip-sweep path
    keys = jnp.asarray(rng.integers(0, K, C), jnp.int32)
    xs = _scalar_vals(C)
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
    ref = per_key_reference(m, keys, _val_list(vals), W)
    assert _tree_equal(both, ref)


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
# Hypothesis property sweep
# ---------------------------------------------------------------------------


def test_keyed_stream_property():
    hyp = pytest.importorskip("hypothesis")
    st_mod = pytest.importorskip("hypothesis.strategies")
    given, settings, st = hyp.given, hyp.settings, st_mod

    @given(
        data=st.data(),
        name=st.sampled_from(sorted(MONOID_CASES)),
        window=st.integers(1, 9),
        chunk=st.integers(2, 24),
        universe=st.integers(1, 12),
    )
    @settings(max_examples=30, deadline=None)
    def run(data, name, window, chunk, universe):
        make, gen = MONOID_CASES[name]
        m = make()
        T = data.draw(st.integers(1, 60))
        local = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
        keys = local.integers(0, universe, T).astype(np.int32)
        if name == "affine_i32":
            vals = (
                jnp.asarray(local.integers(-4, 4, T), jnp.int32),
                jnp.asarray(local.integers(-5, 5, T), jnp.int32),
            )
        elif name == "m4":
            vals = jnp.asarray(local.integers(-9, 9, T), jnp.float32)
        else:
            vals = jnp.asarray(local.integers(-9, 9, T), jnp.int32)
        eng = KeyedChunkedStream(m, window, slots=universe + 1, chunk=chunk)
        _, ys = eng.stream(keys, vals)
        ref = per_key_reference(m, keys, _val_list(vals), window)
        assert _tree_equal(ys, ref)

    run()


def test_keyed_new_key_mix_property():
    """Chunks mixing 0 / few / many genuinely-new keys: the admission fast
    path (no new keys), small batched admissions, and admission-heavy
    chunks all reproduce the per-element reference bit-exactly."""
    hyp = pytest.importorskip("hypothesis")
    st_mod = pytest.importorskip("hypothesis.strategies")
    given, settings, st = hyp.given, hyp.settings, st_mod

    @given(
        data=st.data(),
        name=st.sampled_from(["sum_i32", "affine_i32"]),
        window=st.integers(1, 8),
        chunk=st.integers(4, 16),
    )
    @settings(max_examples=25, deadline=None)
    def run(data, name, window, chunk):
        make, gen = MONOID_CASES[name]
        m = make()
        local = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
        n_chunks = data.draw(st.integers(2, 6))
        # per-chunk count of NEVER-seen keys: 0 → all-hit fast path,
        # small → a one-round batched admission, chunk-many → every row new
        mixes = [
            data.draw(st.sampled_from([0, 1, 2, chunk]))
            for _ in range(n_chunks)
        ]
        next_new = 0
        keys = []
        for n_new in mixes:
            fresh = list(range(next_new, next_new + n_new))
            next_new += n_new
            pool = max(next_new, 1)
            old = local.integers(0, pool, chunk - n_new)
            ck = np.concatenate([np.asarray(fresh, np.int64), old])
            local.shuffle(ck)
            keys.append(ck)
        keys = np.concatenate(keys).astype(np.int32)
        T = len(keys)
        vals = gen(T) if name != "affine_i32" else (
            jnp.asarray(local.integers(-4, 4, T), jnp.int32),
            jnp.asarray(local.integers(-5, 5, T), jnp.int32),
        )
        eng = KeyedChunkedStream(m, window, slots=next_new + chunk + 1,
                                 chunk=chunk)
        _, ys = eng.stream(keys, vals)
        ref = per_key_reference(m, keys, _val_list(vals), window)
        assert _tree_equal(ys, ref)

    run()


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
    wvals, vals = _scalar_vals(chunk), _scalar_vals(6 * chunk)
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
        _val_list(jnp.concatenate([wvals, vals])), W,
    )
    got = jnp.concatenate([y0, ys])
    assert _tree_equal(got, ref)


def test_store_seg_kernel_matches_lax_path():
    """use_seg_kernel=True (Pallas segmented suffix scan, interpret mode on
    CPU) reproduces the default lax path bit-exactly at the store level."""
    m = monoids.sum_monoid(jnp.int32)
    W, chunk, U, T = 6, 32, 11, 300
    keys = rng.integers(0, U, T).astype(np.int32)
    vals = _scalar_vals(T)
    base = KeyedChunkedStream(m, W, slots=U + 1, chunk=chunk)
    kern = KeyedChunkedStream(m, W, slots=U + 1, chunk=chunk,
                              use_seg_kernel=True)
    _, y0 = base.stream(keys, vals)
    _, y1 = kern.stream(keys, vals)
    assert jnp.array_equal(y0, y1)
    ref = per_key_reference(m, keys, _val_list(vals), W)
    assert _tree_equal(y1, ref)
    # a pytree monoid has no scalar op → explicit kernel request is an error
    with pytest.raises(ValueError):
        KeyedWindowStore(monoids.affine_int_monoid(), W, slots=4,
                         use_seg_kernel=True)._seg_scan(
            jnp.zeros(4, bool), (jnp.zeros(4, jnp.int32),) * 2)


# ---------------------------------------------------------------------------
# Directory edge cases
# ---------------------------------------------------------------------------


def test_directory_lookup_and_collisions():
    d = KeyDirectory(slots=8, probes=16)
    st = d.init()
    touched = jnp.zeros((8,), bool)
    keys = [3, 11, 19, 27]  # likely colliding probe chains mod small table
    slots = {}
    for k in keys:
        st, touched, slot, new = d.admit_row(st, touched, k, 1.0)
        assert int(slot) >= 0 and bool(new)
        slots[k] = int(slot)
    assert len(set(slots.values())) == len(keys)  # distinct slots
    got, found = d.lookup(st, jnp.asarray(keys, jnp.int32))
    assert bool(found.all())
    assert [int(s) for s in got] == [slots[k] for k in keys]
    # re-admit finds, does not reallocate
    st, touched, slot, new = d.admit_row(st, touched, 19, 2.0)
    assert int(slot) == slots[19] and not bool(new)
    assert int(st["n_live"]) == len(keys)


def test_directory_lru_eviction_and_tombstone_reuse():
    d = KeyDirectory(slots=2, probes=8)
    st = d.init()
    t = jnp.zeros((2,), bool)
    st, t, s0, _ = d.admit_row(st, t, 100, 1.0)
    st, t, s1, _ = d.admit_row(st, t, 200, 2.0)
    # full; fresh chunk (touched resets) -> key 300 evicts LRU (key 100)
    t = jnp.zeros((2,), bool)
    st, t, s2, new = d.admit_row(st, t, 300, 3.0)
    assert int(s2) == int(s0) and bool(new)
    _, found = d.lookup(st, jnp.asarray([100], jnp.int32))
    assert not bool(found[0])  # tombstoned
    assert int(st["n_evicted"]) == 1
    # the probe chain still reaches key 200 through any tombstone
    got, found = d.lookup(st, jnp.asarray([200, 300], jnp.int32))
    assert bool(found.all()) and int(got[0]) == int(s1)
    # a chunk with every slot touched cannot evict: admission fails safely
    t = jnp.ones((2,), bool)
    st, t, s3, new = d.admit_row(st, t, 400, 4.0)
    assert int(s3) == -1 and not bool(new)
    assert int(st["n_failed"]) == 1


def test_directory_ttl_expire():
    d = KeyDirectory(slots=4)
    st = d.init()
    t = jnp.zeros((4,), bool)
    st, t, _, _ = d.admit_row(st, t, 1, 1.0)
    st, t, _, _ = d.admit_row(st, t, 2, 9.0)
    st, expired = d.expire(st, now=10.0, ttl=5.0)
    assert int(expired.sum()) == 1
    _, found = d.lookup(st, jnp.asarray([1, 2], jnp.int32))
    assert not bool(found[0]) and bool(found[1])
    assert int(st["n_live"]) == 1


def test_store_slot_reuse_resets_window():
    """An evicted tenant's aggregates must never leak into the new tenant."""
    m = monoids.sum_monoid(jnp.int32)
    store = KeyedWindowStore(m, window=4, slots=1)
    st = store.init_state()
    st, ys, _ = store.update_chunk(
        st, jnp.asarray([7, 7], jnp.int32), jnp.asarray([10, 20], jnp.int32)
    )
    assert int(ys[1]) == 30
    # new key evicts key 7 (only slot) and starts from scratch
    st, ys, info = store.update_chunk(
        st, jnp.asarray([8], jnp.int32), jnp.asarray([1], jnp.int32)
    )
    assert int(ys[0]) == 1
    agg, found = store.query(st, jnp.asarray([8, 7], jnp.int32))
    assert int(agg[0]) == 1 and not bool(found[1])
    assert int(st["n_seen"].sum()) == 1  # reset on reuse


def test_store_overflowing_chunk_drops_excess_keys():
    m = monoids.sum_monoid(jnp.int32)
    store = KeyedWindowStore(m, window=4, slots=2)
    st = store.init_state()
    keys = jnp.arange(6, dtype=jnp.int32)  # 6 distinct keys, 2 slots
    st, ys, info = store.update_chunk(st, keys, jnp.ones(6, jnp.int32))
    assert int(info["n_live"]) == 2
    assert int(st["n_dropped"]) == 4
    assert int(info["dropped"].sum()) == 4
    # dropped rows emit identities
    assert int(jnp.where(info["dropped"], ys, 0).sum()) == 0


def test_store_ttl_sweep_inside_update():
    m = monoids.sum_monoid(jnp.int32)
    store = KeyedWindowStore(m, window=4, slots=4, ttl=5.0)
    st = store.init_state()
    st, _, _ = store.update_chunk(
        st, jnp.asarray([1], jnp.int32), jnp.ones(1, jnp.int32), ts=1.0
    )
    st, _, _ = store.update_chunk(
        st, jnp.asarray([2], jnp.int32), jnp.ones(1, jnp.int32), ts=10.0
    )
    _, found = store.query(st, jnp.asarray([1, 2], jnp.int32))
    assert not bool(found[0]) and bool(found[1])


# ---------------------------------------------------------------------------
# SWAG interop through the carry protocol
# ---------------------------------------------------------------------------


def test_export_states_continue_per_element():
    """A key's window exported to DABA-Lite continues element-for-element."""
    m = monoids.affine_int_monoid()
    W = 6
    T, U = 80, 5
    keys = rng.integers(0, U, T).astype(np.int32)
    vals = _affine_vals(T)
    store = KeyedWindowStore(m, W, slots=U)
    st = store.init_state()
    st, _, _ = store.update_chunk(st, keys, vals)
    states, found = store.export_states(st, jnp.arange(U, dtype=jnp.int32), daba_lite)
    assert bool(found.all())
    # the reconstructed window holds the key's last W-1 elements: its query
    # must equal the reference fold of those elements
    vlist = _val_list(vals)
    for k in range(U):
        mine = [vlist[i] for i in range(T) if int(keys[i]) == k][-(W - 1):]
        acc = m.identity()
        for v in mine:
            acc = m.combine(acc, m.lift(v))
        got = daba_lite.query(m, jax.tree.map(lambda a: a[k], states))
        assert _tree_equal(got, acc)


def test_adopt_states_roundtrip():
    m = monoids.sum_monoid(jnp.int32)
    W = 5
    # build live per-element windows for 3 keys
    sts = []
    expected = []
    for k in range(3):
        s = daba_lite.init(m, W + 2)
        vals = rng.integers(-9, 9, 4 + k)
        for v in vals:
            s = daba_lite.insert(m, s, int(v))
            if int(daba_lite.size(s)) > W - 1:
                s = daba_lite.evict(m, s)
        sts.append(s)
        expected.append(int(daba_lite.query(m, s)))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *sts)
    store = KeyedWindowStore(m, W, slots=4)
    st = store.adopt_states(store.init_state(), jnp.asarray([10, 11, 12]), stacked, daba_lite)
    agg, found = store.query(st, jnp.asarray([10, 11, 12], jnp.int32))
    assert bool(found.all())
    assert [int(a) for a in agg] == expected


# ---------------------------------------------------------------------------
# Segmented suffix scan (unit)
# ---------------------------------------------------------------------------


def test_seg_suffix_scan_non_commutative():
    m = monoids.affine_int_monoid()
    vals = _affine_vals(9)
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
            assert _tree_equal(got, acc)


# ---------------------------------------------------------------------------
# Keyed telemetry
# ---------------------------------------------------------------------------


def test_keyed_telemetry_and_state_dict():
    metrics = {"lat": monoids.mean_monoid(), "mx": monoids.max_monoid()}
    kt = KeyedTelemetry(metrics, window=3, slots=8)
    kt.observe_bulk(
        jnp.asarray([1, 2, 1, 1, 1], jnp.int32),
        {
            "lat": jnp.asarray([1.0, 5.0, 2.0, 3.0, 4.0]),
            "mx": jnp.asarray([1.0, 5.0, 2.0, 3.0, 4.0]),
        },
    )
    s = kt.snapshot([1, 2, 9])
    assert bool(s["found"][0]) and bool(s["found"][1]) and not bool(s["found"][2])
    assert abs(float(s["lat"][0]) - 3.0) < 1e-6  # window=3: mean(2,3,4)
    assert float(s["mx"][0]) == 4.0 and float(s["mx"][1]) == 5.0
    assert set(kt.live_keys()) == {1, 2}
    # round trip through state_dict
    kt2 = KeyedTelemetry(metrics, window=3, slots=8)
    kt2.load_state_dict(kt.state_dict())
    s2 = kt2.snapshot([1, 2])
    assert float(s2["lat"][0]) == float(s["lat"][0])
    # mismatched configuration is rejected
    kt3 = KeyedTelemetry(metrics, window=3, slots=16)
    with pytest.raises(ValueError):
        kt3.load_state_dict(kt.state_dict())


# ---------------------------------------------------------------------------
# Sharded store (multi-device subprocess)
# ---------------------------------------------------------------------------

_SUBPROCESS_SHARDED = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import monoids
    from repro.core.keyed import KeyedChunkedStream, ShardedKeyedStore

    rng = np.random.default_rng(1)
    T, U, W = 256, 40, 8
    keys = rng.integers(0, U, T).astype(np.int32)
    xs = rng.integers(-9, 9, T).astype(np.int32)
    m = monoids.sum_monoid(jnp.int32)

    mesh = jax.make_mesh((4,), ("data",))
    sh = ShardedKeyedStore(m, W, slots_per_shard=32, mesh=mesh, axis="data")
    state = sh.init_state()
    state, ys, owner = sh.update_chunk(state, jnp.asarray(keys), jnp.asarray(xs))
    y = ShardedKeyedStore.collect(ys, owner)

    eng = KeyedChunkedStream(m, window=W, slots=128, chunk=T)
    _, ref = eng.stream(keys, jnp.asarray(xs))
    assert jnp.array_equal(y, ref)
    # per-shard states are genuinely sharded on the leading axis
    assert state["carry"].sharding.spec[0] == "data"
    # init_state builds each shard on its own device (no stacked copy)
    shards = state["carry"].addressable_shards
    assert sorted(s.device.id for s in shards) == [0, 1, 2, 3]
    assert all(s.data.shape[0] == 1 for s in shards)
    print("OK")
    """
)


def test_sharded_default_ts_keeps_recency():
    """Default (no ts) sharded updates must advance last_used via the
    per-shard tick: a hot key observed every chunk is never TTL-expired,
    and the untouched key (not a hot one) is the LRU/TTL victim."""
    from repro.core.keyed import ShardedKeyedStore

    m = monoids.sum_monoid(jnp.int32)
    mesh = jax.make_mesh((1,), ("data",))
    sh = ShardedKeyedStore(m, 4, slots_per_shard=8, mesh=mesh, axis="data",
                           ttl=5.0)
    st = sh.init_state()
    st, _, _ = sh.update_chunk(st, jnp.asarray([1, 2], jnp.int32),
                               jnp.ones(2, jnp.int32))
    for _ in range(8):  # key 1 stays hot; key 2 goes idle past the ttl
        st, _, _ = sh.update_chunk(st, jnp.asarray([1], jnp.int32),
                                   jnp.ones(1, jnp.int32))
    st1 = jax.tree.map(lambda a: a[0], st)
    agg, found = sh.store.query(st1, jnp.asarray([1, 2], jnp.int32))
    assert bool(found[0]), "hot key must survive TTL sweeps"
    assert not bool(found[1]), "idle key should expire"
    assert int(agg[0]) == 4  # window of the hot key's last 4 ones


def test_directory_lookup_negative_keys_never_found():
    d = KeyDirectory(slots=4)
    st = d.init()
    t = jnp.zeros((4,), bool)
    st, t, _, _ = d.admit_row(st, t, 0, 1.0)
    _, found = d.lookup(st, jnp.asarray([-1, -2, 0], jnp.int32))
    assert not bool(found[0]) and not bool(found[1]) and bool(found[2])


def test_sharded_keyed_store_4dev():
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SHARDED],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# Event-time horizon mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["sum_i32", "max_i32", "affine_i32", "m4"])
@pytest.mark.parametrize("window,chunk,horizon", [
    (5, 16, 7.0),    # expiry inside count-capped spans
    (16, 8, 3.0),    # window > chunk: carry lanes cross chunk boundaries
    (1, 16, 2.0),    # degenerate count window
    (9, 16, 1000.0), # horizon never binds → count semantics
])
def test_keyed_horizon_matches_timestamped_reference(name, window, chunk,
                                                     horizon):
    """Event-time ``horizon=`` windows ≡ the per-key timestamped dict
    oracle, bit-exactly, for integer AND non-commutative monoids — both
    when expiry bites mid-carry and when the horizon never binds."""
    make, gen = MONOID_CASES[name]
    m = make()
    T, U = 200, 13
    keys = rng.integers(0, U, T).astype(np.int32)
    ts = np.cumsum(rng.integers(0, 3, T)).astype(np.float32)  # ties allowed
    vals = gen(T)
    eng = KeyedChunkedStream(m, window, slots=U + 3, chunk=chunk,
                             horizon=horizon)
    _, ys = eng.stream(keys, vals, ts=jnp.asarray(ts))
    ref = per_key_horizon_reference(m, keys, _val_list(vals), ts, window,
                                    horizon)
    assert _tree_equal(ys, ref)


def test_keyed_horizon_warm_continuation_expires_carry():
    """Chunk-boundary expiry: history admitted in an earlier stream() call
    is dropped by a later call's watermark purely through the ``carry_ts``
    lanes (ONE extra gather/scatter — the donation rule holds)."""
    m = monoids.sum_monoid(jnp.int32)
    eng = KeyedChunkedStream(m, 8, slots=4, chunk=4, horizon=5.0)
    keys = np.zeros(4, np.int32)
    st, ys = eng.stream(keys, jnp.ones(4, jnp.int32),
                        ts=jnp.asarray([0.0, 1.0, 2.0, 3.0]))
    assert np.asarray(ys).tolist() == [1, 2, 3, 4]
    # second call: ts=6 retains {2, 3, 6} (> 6 - 5 = 1); ts=100 only itself
    st, ys = eng.stream(keys[:2], jnp.ones(2, jnp.int32),
                        ts=jnp.asarray([6.0, 100.0]), state=st)
    assert np.asarray(ys).tolist() == [3, 1]


def test_keyed_horizon_property():
    """Hypothesis sweep: horizon mode ≡ the timestamped per-key oracle for
    ANY globally non-decreasing integer timestamp stream (ties included),
    any key mix, window, chunk split, and horizon."""
    hyp = pytest.importorskip("hypothesis")
    st_mod = pytest.importorskip("hypothesis.strategies")
    given, settings, st = hyp.given, hyp.settings, st_mod

    @given(
        data=st.data(),
        name=st.sampled_from(sorted(MONOID_CASES)),
        window=st.integers(1, 9),
        chunk=st.integers(2, 24),
        universe=st.integers(1, 8),
        horizon=st.integers(1, 20),
    )
    @settings(max_examples=30, deadline=None)
    def run(data, name, window, chunk, universe, horizon):
        make, gen = MONOID_CASES[name]
        m = make()
        T = data.draw(st.integers(1, 60))
        local = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
        keys = local.integers(0, universe, T).astype(np.int32)
        ts = np.cumsum(local.integers(0, 4, T)).astype(np.float32)
        if name == "affine_i32":
            vals = (
                jnp.asarray(local.integers(-4, 4, T), jnp.int32),
                jnp.asarray(local.integers(-5, 5, T), jnp.int32),
            )
        elif name == "m4":
            vals = jnp.asarray(local.integers(-9, 9, T), jnp.float32)
        else:
            vals = jnp.asarray(local.integers(-9, 9, T), jnp.int32)
        eng = KeyedChunkedStream(m, window, slots=universe + 1, chunk=chunk,
                                 horizon=float(horizon))
        _, ys = eng.stream(keys, vals, ts=jnp.asarray(ts))
        ref = per_key_horizon_reference(m, keys, _val_list(vals), ts, window,
                                        float(horizon))
        assert _tree_equal(ys, ref)

    run()
