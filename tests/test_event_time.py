"""Event-time engine ≡ in-order reference.

The load-bearing property: for ANY stream whose disorder is bounded by
the engine's slack, the bulk out-of-order engine's released outputs
equal — bit-exactly for integer/selection monoids, including
NON-commutative ones — the per-element in-order scan of the
timestamp-sorted stream, across disorder levels, integer timestamps and
the DisorderedEventStream generator.  The hypothesis sweep of the same
property is test_event_time_property.py; engine edge cases (chunk splits,
gaps, late chunks, continuation) are in test_event_time_windows.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import monoids
from repro.core.event_time import EventTimeChunkedStream, in_order_reference
from repro.data.stream import DisorderedEventStream

from event_time_cases import MONOID_CASES, assert_tree_close, disordered


# ---------------------------------------------------------------------------
# Engine ≡ in-order reference whenever disorder ≤ slack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mname", sorted(MONOID_CASES))
@pytest.mark.parametrize("disorder,slack", [(0.0, 0.0), (0.3, 9.0), (0.8, 25.0)])
def test_engine_matches_in_order_reference(mname, disorder, slack):
    m, mk, exact = MONOID_CASES[mname]
    T, B, horizon = 70, 2, 17.0
    # deterministic per-case seed (str hash is randomized per process)
    seed = sum(map(ord, mname)) * 100 + int(disorder * 10)
    ats, order = disordered(T, disorder, slack, seed=seed)
    xs = mk((T, B))
    axs = jax.tree.map(lambda a: a[order], xs)
    eng = EventTimeChunkedStream(
        m, horizon, slack=slack, chunk=16, capacity=64, buffer=32
    )
    res = eng.stream(jnp.asarray(ats), axs)
    assert res.n_late == 0 and res.n_dropped == 0
    ref_ts, ref_ys = in_order_reference(m, ats, axs, horizon)
    assert np.array_equal(res.ts, ref_ts)
    assert_tree_close(res.ys, ref_ys, exact, (mname, disorder, slack))


@pytest.mark.parametrize("mname", ["sum_i32", "affine_i32"])
def test_engine_integer_timestamps_bit_exact(mname):
    """Integer event times through the int32 sentinel arithmetic."""
    m, mk, _ = MONOID_CASES[mname]
    T, B = 60, 2
    ats, order = disordered(T, 0.4, 6, seed=11, int_ts=True)
    axs = jax.tree.map(lambda a: a[order], mk((T, B)))
    eng = EventTimeChunkedStream(
        m, 9, slack=6, chunk=13, capacity=64, buffer=16, ts_dtype=jnp.int32
    )
    res = eng.stream(jnp.asarray(ats), axs)
    ref_ts, ref_ys = in_order_reference(m, ats, axs, 9)
    assert np.array_equal(res.ts, ref_ts)
    assert_tree_close(res.ys, ref_ys, exact=True, ctx=mname)


def test_disordered_event_stream_generator_equivalence():
    """The data-layer generator's lateness bound feeds the engine exactly."""
    stream = DisorderedEventStream(
        120, batch=2, disorder=0.4, slack=6.0, integer_values=True, seed=5
    )
    ats, axs = stream.arrival()
    assert stream.max_lateness() <= 6.0
    m = monoids.sum_monoid(jnp.int32)
    eng = EventTimeChunkedStream(
        m, 20.0, slack=6.0, chunk=32, capacity=128, buffer=32
    )
    res = eng.stream(ats, axs)
    ref_ts, ref_ys = in_order_reference(m, ats, axs, 20.0)
    assert np.array_equal(res.ts, ref_ts)
    assert_tree_close(res.ys, ref_ys, exact=True)
    assert res.n_late == 0
