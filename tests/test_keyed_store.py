"""Keyed store internals: ``KeyDirectory`` collision, LRU-eviction,
TTL-expiry and negative-key edge cases; window-lane reset on slot reuse
(no cross-tenant leakage) and dropped overflow rows; the carry refresh's
lane shifts and row slices, bit-exact at every shift boundary and lowered
without element-index gathers; SWAG interop through the warm carry
protocol (``export_states`` / ``adopt_states``); and ``KeyedTelemetry``
per-key metrics with their state_dict round trip."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daba_lite, monoids
from repro.core.keyed import KeyDirectory, KeyedChunkedStream, KeyedWindowStore
from repro.core.reference import per_key_horizon_reference, per_key_reference
from repro.core.telemetry import KeyedTelemetry

from keyed_cases import MONOID_CASES, affine_vals, rng, tree_equal, val_list


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


def test_directory_lookup_negative_keys_never_found():
    d = KeyDirectory(slots=4)
    st = d.init()
    t = jnp.zeros((4,), bool)
    st, t, _, _ = d.admit_row(st, t, 0, 1.0)
    _, found = d.lookup(st, jnp.asarray([-1, -2, 0], jnp.int32))
    assert not bool(found[0]) and not bool(found[1]) and bool(found[2])


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
# Carry refresh: lane shifts and from-chunk row slices
# ---------------------------------------------------------------------------

# (window, chunk, slots, chunks), each chunk a list of (key, segment
# length).  The lengths hit 1, 2**k - 1, 2**k, h - 1, h and > h where a
# chunk holds them.  Each later key group takes reclaimed slots of evicted
# keys, which never return, so the per-key oracles stay exact.
REFRESH_LAYOUTS = {
    # W <= C: h = 7, every lane can come from the chunk (h0 = 0)
    "h0_zero": (8, 32, 10, [
        list(zip(range(0, 7), [1, 3, 4, 6, 7, 9, 2])),
        list(zip(range(0, 7), [9, 7, 6, 4, 3, 1, 2])),
        list(zip(range(7, 14), [2, 7, 1, 9, 6, 4, 3])),
        list(zip(range(7, 14), [6, 3, 9, 1, 4, 7, 2])),
        list(zip(range(14, 21), [4, 1, 2, 3, 7, 6, 9])),
    ]),
    # W > C: h = 17, the chunk fills only the last 16 lanes (h0 = 1)
    "h0_pos": (18, 16, 6, [
        list(zip(range(0, 4), [1, 3, 4, 8])),
        [(3, 16)],
        list(zip(range(0, 4), [8, 4, 3, 1])),
        list(zip(range(4, 7), [7, 1, 8])),
        list(zip(range(4, 6), [15, 1])),
        list(zip(range(7, 11), [2, 2, 4, 8])),
    ]),
}


def _expected_lanes(m, hist, h):
    """Lane t of a key's carry folds its last min(h - t, n) lifted elements
    (older operand LEFT); its ``carry_ts`` lane holds the timestamp of the
    (h - t)-th element from the last, -inf where there is none."""
    lanes, lane_ts = [None] * h, [-np.inf] * h
    acc = None
    for need in range(1, h + 1):
        if need <= len(hist):
            t_e, e = hist[-need]
            acc = e if acc is None else m.combine(e, acc)
            lane_ts[h - need] = t_e
        lanes[h - need] = acc
    return jax.tree.map(lambda *xs: jnp.stack(xs), *lanes), lane_ts


@pytest.mark.parametrize("name", ["max_i32", "sum_i32", "affine_i32"])
@pytest.mark.parametrize("horizon", [None, 0.75], ids=["count", "horizon"])
@pytest.mark.parametrize("layout", sorted(REFRESH_LAYOUTS))
def test_carry_refresh_bit_exact_at_shift_boundaries(layout, horizon, name):
    """Per-row folds and the stored ``carry`` / ``carry_ts`` lanes after
    every chunk equal the per-key oracles, for segment lengths on each bit
    boundary of the lane shift, new keys on reclaimed slots, both static
    lane splits and both window modes."""
    W, C, S, chunks = REFRESH_LAYOUTS[layout]
    make, gen = MONOID_CASES[name]
    m = make()
    hz = None if horizon is None else horizon * C
    eng = KeyedChunkedStream(m, W, slots=S, chunk=C, horizon=hz)
    store, h = eng.store, W - 1
    st = eng.init_state()
    all_keys, all_vals, all_ts, ys = [], [], [], []
    hist: dict = {}
    t_now = 0.0
    for layout_c in chunks:
        keys = np.concatenate([[k] * n for k, n in layout_c]).astype(np.int32)
        keys = rng.permutation(keys)
        assert keys.size == C
        vals = gen(C)
        ts = t_now + np.cumsum(rng.integers(0, 3, C)).astype(np.float32)
        t_now = float(ts[-1])
        st, y, _ = eng.process_chunk(
            st, keys, vals, None if hz is None else jnp.asarray(ts)
        )
        ys.append(y)
        all_keys.append(keys)
        all_ts.append(ts)
        all_vals += val_list(vals)
        for k, v, t in zip(keys, val_list(vals), ts):
            hist.setdefault(int(k), []).append((float(t), m.lift(v)))
        live = sorted({k for k, _ in layout_c})
        slot, found = store.directory.lookup(
            st["dir"], jnp.asarray(live, jnp.int32)
        )
        assert bool(found.all())
        for k, s in zip(live, np.asarray(slot)):
            lanes, lane_ts = _expected_lanes(m, hist[k], h)
            assert tree_equal(jax.tree.map(lambda c: c[s], st["carry"]), lanes)
            if hz is not None:
                assert np.asarray(st["carry_ts"][s]).tolist() == lane_ts
    assert int(st["dir"]["n_evicted"]) > 0  # new heads took reclaimed slots
    keys = np.concatenate(all_keys)
    if hz is None:
        ref = per_key_reference(m, keys, all_vals, W)
    else:
        ref = per_key_horizon_reference(
            m, keys, all_vals, np.concatenate(all_ts), W, hz
        )
    got = jax.tree.map(lambda *parts: jnp.concatenate(parts), *ys)
    assert tree_equal(got, ref)


_GATHER = re.compile(
    r'"stablehlo\.gather".*slice_sizes = array<i64: ([\d, ]+)>.*'
    r"-> tensor<([\dx]+)x\w+>"
)


@pytest.mark.parametrize("name", ["max_i32", "sum_i32", "affine_i32"])
def test_carry_refresh_lowers_without_lane_element_gathers(name):
    """No (C, h) lane realignment of the refresh is an element-index
    gather (``slice_sizes`` 1 on the lane axis): on a TPU those cost about
    2,000x their bytes, while the row gathers of the same rows are cheap.
    Lowered to StableHLO only; nothing compiles."""
    C, W = 1024, 256
    store = KeyedWindowStore(MONOID_CASES[name][0](), W, slots=4096,
                             horizon=10.0)
    spec = jax.ShapeDtypeStruct
    xs = jax.tree.map(lambda x: spec(x.shape, x.dtype),
                      MONOID_CASES[name][1](C))
    text = jax.jit(store.update_chunk).lower(
        jax.eval_shape(store.init_state), spec((C,), jnp.int32), xs,
        spec((C,), jnp.float32), spec((C,), bool),
    ).as_text()
    gathers = [
        (tuple(int(s) for s in g[0].split(",")),
         tuple(int(d) for d in g[1].split("x")))
        for g in _GATHER.findall(text)
    ]
    lane_sized = [g for g in gathers if g[1][:2] == (C, store.h)]
    assert lane_sized  # the carry row gathers are found at all
    assert all(max(sizes) > 1 for sizes, _ in lane_sized), lane_sized


# ---------------------------------------------------------------------------
# SWAG interop through the carry protocol
# ---------------------------------------------------------------------------


def test_export_states_continue_per_element():
    """A key's window exported to DABA-Lite continues element-for-element."""
    m = monoids.affine_int_monoid()
    W = 6
    T, U = 80, 5
    keys = rng.integers(0, U, T).astype(np.int32)
    vals = affine_vals(T)
    store = KeyedWindowStore(m, W, slots=U)
    st = store.init_state()
    st, _, _ = store.update_chunk(st, keys, vals)
    states, found = store.export_states(st, jnp.arange(U, dtype=jnp.int32), daba_lite)
    assert bool(found.all())
    # the reconstructed window holds the key's last W-1 elements: its query
    # must equal the reference fold of those elements
    vlist = val_list(vals)
    for k in range(U):
        mine = [vlist[i] for i in range(T) if int(keys[i]) == k][-(W - 1):]
        acc = m.identity()
        for v in mine:
            acc = m.combine(acc, m.lift(v))
        got = daba_lite.query(m, jax.tree.map(lambda a: a[k], states))
        assert tree_equal(got, acc)


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
