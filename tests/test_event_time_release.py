"""Event-time release paths: the disorder-adaptive release (no-sort merge
at zero disorder, bounded merge above) is invisible in the outputs, its
branch and disorder counters read what ran, and the flip sweep's combines
per merge position stay flat as the horizon grows."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import monoids
from repro.core.event_time import EventTimeChunkedStream, in_order_reference
from repro.obs import counters as obs_counters

from event_time_cases import (
    MONOID_CASES,
    assert_tree_close,
    disordered,
    rng,
    scalar_vals,
)


# ---------------------------------------------------------------------------
# Disorder-adaptive release path
# ---------------------------------------------------------------------------


def _adversarial_max_late(T, slack, *, seed):
    """Every other row delayed by EXACTLY ``slack``: each chunk mixes
    frontier rows with maximally-late ones, so no chunk is in-order and the
    bounded merge runs at its admissible-distance ceiling.  (An all-equal
    delay would leave the stream in-order — the alternation is the point.)"""
    r = np.random.default_rng(seed)
    ts = np.sort(r.integers(0, 3 * T, T)).astype(np.float32)
    delay = np.float32(slack) * (np.arange(T) % 2).astype(np.float32)
    order = np.argsort(ts + delay, kind="stable")
    return ts[order], order


HORIZON, SLACK = 21.0, 9.0  # of the adaptive-release cases


@pytest.fixture(scope="module")
def release_engine():
    """One engine per monoid, shared by that monoid's disorder cases: the
    cases differ only in their streams, so the compiled chunk programs are
    reused instead of rebuilt."""
    engines = {}

    def get(mname):
        if mname not in engines:
            engines[mname] = EventTimeChunkedStream(
                MONOID_CASES[mname][0], HORIZON, slack=SLACK, chunk=16,
                capacity=160, buffer=64,
            )
        return engines[mname]

    return get


@pytest.mark.parametrize("mname", ["affine_i32", "m4_int", "argmax"])
@pytest.mark.parametrize("disorder", [0.0, 0.1, 0.5, "max_late"])
def test_adaptive_release_path_bit_exact(mname, disorder, release_engine):
    """The disorder-adaptive release path (no-sort compact merge at d = 0,
    bounded merge above) is invisible in the outputs: bit-exact vs the
    in-order reference for NON-commutative monoids across disorder levels —
    including the adversarial alternating maximally-late stream — with a
    ragged final chunk (T % chunk != 0)."""
    m, mk, _ = MONOID_CASES[mname]
    T, B = 75, 2
    seed = sum(map(ord, mname)) + (97 if disorder == "max_late"
                                   else int(disorder * 10))
    if disorder == "max_late":
        ats, order = _adversarial_max_late(T, SLACK, seed=seed)
    else:
        ats, order = disordered(T, disorder, SLACK, seed=seed)
    xs = mk((T, B))
    axs = jax.tree.map(lambda a: a[order], xs)
    res = release_engine(mname).stream(jnp.asarray(ats), axs)
    assert res.n_late == 0 and res.n_dropped == 0
    ref_ts, ref_ys = in_order_reference(m, ats, axs, HORIZON)
    assert np.array_equal(res.ts, ref_ts)
    assert_tree_close(res.ys, ref_ys, exact=True, ctx=(mname, disorder))


def test_release_branch_counters_zero_sorts_in_order():
    """Fast-path regression guard: an in-order stream must dispatch ZERO
    sorting (slow) release branches — every chunk, including the flush
    drain, rides the no-sort compact merge — while a disordered stream must
    take the slow branch at least once.  Branch taken is counted per chunk
    in ``obs.counters.releases`` when ``instrument_release=True``."""
    m = monoids.sum_monoid(jnp.int32)
    T, B = 96, 1
    ts = np.sort(rng.uniform(0, 200.0, T)).astype(np.float32)
    xs = scalar_vals((T, B), jnp.int32)
    eng = EventTimeChunkedStream(m, 20.0, slack=4.0, chunk=16, capacity=96,
                                 buffer=32, instrument_release=True)
    obs_counters.releases.reset()
    res = eng.stream(jnp.asarray(ts), xs)
    counts = obs_counters.releases.read()  # read() barriers the callbacks
    assert counts["slow"] == 0
    assert counts["fast"] >= T // 16  # every full chunk counted
    ref_ts, ref_ys = in_order_reference(m, ts, xs, 20.0)
    assert np.array_equal(res.ts, ref_ts)
    assert_tree_close(res.ys, ref_ys, exact=True)

    ats, order = disordered(T, 0.5, 4.0, seed=3)
    axs = jax.tree.map(lambda a: a[order], xs)
    obs_counters.releases.reset()
    eng.stream(jnp.asarray(ats), axs)
    counts = obs_counters.releases.read()
    assert counts["slow"] > 0


def test_ooo_distance_gauges_track_measured_disorder():
    """``obs_metrics`` exposes the measured out-of-order distance of recent
    chunks: zero across an in-order stream, positive once a disordered one
    has been processed (the slow branch records the exact displacement)."""
    m = monoids.sum_monoid(jnp.int32)
    T = 64
    ts = np.sort(rng.uniform(0, 100.0, T)).astype(np.float32)
    xs = scalar_vals((T, 1), jnp.int32)
    eng = EventTimeChunkedStream(m, 20.0, slack=8.0, chunk=16, capacity=96,
                                 buffer=64)
    res = eng.stream(jnp.asarray(ts), xs)
    metrics = eng.obs_metrics(res.state)
    assert int(metrics["ooo_distance_max"]) == 0
    assert float(metrics["ooo_distance_p95"]) == 0.0

    ats, order = disordered(T, 0.5, 8.0, seed=11)
    axs = jax.tree.map(lambda a: a[order], xs)
    res = eng.stream(jnp.asarray(ats), axs)
    metrics = eng.obs_metrics(res.state)
    assert int(metrics["ooo_distance_max"]) > 0


def test_eventtime_combines_per_position_flat_in_horizon():
    """The constant-combine claim, measured at runtime: ⊗-invocations per
    swept merge position stay flat as the horizon (and window capacity)
    grow — the retired doubling table grew as log2(W+C)."""
    T, B, chunk, buffer = 512, 1, 64, 32
    ts = np.sort(rng.uniform(0, float(T), T)).astype(np.float32)
    xs = jnp.asarray(rng.standard_normal((T, B)), jnp.float32)
    per_pos = {}
    for horizon in (8.0, 64.0, 512.0):
        cap = 2 * int(horizon) + 32
        eng = EventTimeChunkedStream(
            monoids.max_monoid(), horizon, slack=0.0, chunk=chunk,
            capacity=cap, buffer=buffer, instrument_combines=True,
        )
        obs_counters.combines.reset()
        eng.stream(jnp.asarray(ts), xs)
        # each chunk sweeps M = capacity + buffer + chunk merge positions;
        # the chunk count is identical across horizons, so it cancels
        # (read() runs effects_barrier before snapshotting)
        per_pos[horizon] = (
            obs_counters.combines.read()["eventtime"] / (cap + buffer + chunk)
        )
    lo, hi = min(per_pos.values()), max(per_pos.values())
    assert lo > 0, per_pos  # the instrumentation actually fired
    assert hi <= 1.5 * lo, per_pos
    # absolute guard: the flip sweep measures ~38 here (9 chunk sweeps of
    # ~4.3 ⊗/position); re-adding a doubling table would roughly triple it
    assert hi <= 60, per_pos
