"""Keyed event-time ``horizon=`` windows ≡ the per-key timestamped oracle,
bit-exactly: expiry inside count-capped spans and across chunk
boundaries, and a hypothesis sweep over timestamp streams."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import monoids
from repro.core.keyed import KeyedChunkedStream
from repro.core.reference import per_key_horizon_reference

from keyed_cases import MONOID_CASES, rng, tree_equal, val_list


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
    ref = per_key_horizon_reference(m, keys, val_list(vals), ts, window,
                                    horizon)
    assert tree_equal(ys, ref)


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
        ref = per_key_horizon_reference(m, keys, val_list(vals), ts, window,
                                        float(horizon))
        assert tree_equal(ys, ref)

    run()
