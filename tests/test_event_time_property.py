"""Event-time engine ≡ in-order reference, as a hypothesis property: any
timestamp and value sequence whose disorder is within the slack."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import monoids
from repro.core.event_time import EventTimeChunkedStream, in_order_reference

from event_time_cases import assert_tree_close


# ---------------------------------------------------------------------------
# Hypothesis sweep
# ---------------------------------------------------------------------------


def test_property_disorder_equivalence_hypothesis():
    """Hypothesis: ANY ts/value sequence with disorder ≤ slack reproduces
    the sorted in-order reference bit-exactly (non-commutative affine_i32)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    m = monoids.affine_int_monoid()

    @settings(max_examples=25, deadline=None)
    @given(data=st.data())
    def inner(data):
        T = data.draw(st.integers(2, 28))
        gaps = data.draw(
            st.lists(st.integers(0, 7), min_size=T, max_size=T)
        )
        ts = np.cumsum(np.asarray(gaps, np.int64)).astype(np.int32)
        slack = data.draw(st.integers(0, 10))
        delays = data.draw(
            st.lists(st.integers(0, max(slack, 0)), min_size=T, max_size=T)
        )
        order = np.argsort(ts + np.asarray(delays, np.int32), kind="stable")
        horizon = data.draw(st.integers(1, 12))
        a = np.asarray(
            data.draw(st.lists(st.integers(-4, 4), min_size=T, max_size=T)),
            np.int32,
        )
        b = np.asarray(
            data.draw(st.lists(st.integers(-4, 4), min_size=T, max_size=T)),
            np.int32,
        )
        xs = (jnp.asarray(a[:, None]), jnp.asarray(b[:, None]))
        axs = jax.tree.map(lambda v: v[order], xs)
        ats = ts[order]
        eng = EventTimeChunkedStream(
            m, horizon, slack=slack, chunk=8, capacity=T + 2, buffer=T + 2,
            ts_dtype=jnp.int32,
        )
        res = eng.stream(jnp.asarray(ats), axs)
        assert res.n_late == 0
        ref_ts, ref_ys = in_order_reference(m, ats, axs, horizon)
        assert np.array_equal(res.ts, ref_ts)
        assert_tree_close(res.ys, ref_ys, exact=True)

    inner()
