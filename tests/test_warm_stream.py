"""Warm routing of ``BatchedSWAG.stream``: chunked-from-warm ≡ per-element.

Chunked ≡ per-element from live (and ragged per-lane) windows, across
ragged chunk splits, both the Pallas-kernel path (scalar ops) and the
generic pytree path; auto routing at the real threshold and under jit;
the ragged-last-chunk identity padding reuses one compilation, and masked
positions are identities.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ALGORITHMS, CONSTANT_TIME_ALGORITHMS, monoids
from repro.core.batched import BatchedSWAG
from repro.core.chunked import ChunkedStream

from warm_cases import (
    MONOID_CASES,
    assert_tree_close,
    check_warm_stream_matches_per_element,
    scalar_vals,
)


# ---------------------------------------------------------------------------
# BatchedSWAG.stream: warm routing ≡ per-element
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mname", sorted(MONOID_CASES))
@pytest.mark.parametrize("algo_name", sorted(CONSTANT_TIME_ALGORITHMS))
def test_warm_stream_chunked_matches_per_element(algo_name, mname):
    """The paper's worst-case O(1) algorithms; the baselines are in
    test_warm_stream_baselines.py."""
    check_warm_stream_matches_per_element(algo_name, mname)


def test_warm_stream_ragged_lanes():
    """Per-lane warm sizes differ (masked fills) — carries are extracted and
    front-truncated per lane."""
    m = monoids.sum_monoid(jnp.int32)
    b = BatchedSWAG(ALGORITHMS["daba_lite"], m, 12)
    st = b.init(3)
    for t in range(6):
        do_ins = jnp.asarray([True, t < 2, t < 5])
        st = b.step(st, scalar_vals((3,), jnp.int32), do_ins, jnp.zeros(3, bool))
    assert sorted(np.asarray(b.size(st)).tolist()) == [2, 5, 6]
    xs = scalar_vals((41, 3), jnp.int32)
    st_pe, ys_pe = b.stream(st, xs, 8, chunked=False)
    st_ch, ys_ch = b.stream(st, xs, 8, chunked=True, chunk=16)
    assert_tree_close(ys_ch, ys_pe, exact=True)
    assert_tree_close(b.query(st_ch), b.query(st_pe), exact=True)


def test_auto_routing_includes_warm_states(monkeypatch):
    """A warm concrete state with T ≥ the auto threshold takes the chunked
    path (engine cache populated); oversized lanes fall back."""
    from repro.core import batched as batched_mod

    monkeypatch.setattr(batched_mod, "CHUNKED_AUTO_MIN_T", 32)
    m = monoids.sum_monoid(jnp.int32)
    b = BatchedSWAG(ALGORITHMS["daba_lite"], m, 12)
    st = b.init(2)
    st, _ = b.stream(st, scalar_vals((10, 2), jnp.int32), 8, chunked=False)
    assert not b._chunked_engines
    xs = scalar_vals((40, 2), jnp.int32)
    st_ch, ys_ch = b.stream(st, xs, 8)
    assert b._chunked_engines, "warm stream should auto-route through chunked"
    _, ys_pe = b.stream(st, xs, 8, chunked=False)
    assert_tree_close(ys_ch, ys_pe, exact=True)


def test_warm_auto_routing_at_real_threshold_exact():
    """No monkeypatching: a warm stream at T ≥ CHUNKED_AUTO_MIN_T takes the
    chunked engine and matches the per-element scan bit-exactly (int sum)."""
    from repro.core.batched import CHUNKED_AUTO_MIN_T

    m = monoids.sum_monoid(jnp.int32)
    b = BatchedSWAG(ALGORITHMS["daba_lite"], m, 36)
    st = b.init(2)
    st, _ = b.stream(st, scalar_vals((40, 2), jnp.int32), 32, chunked=False)
    xs = scalar_vals((CHUNKED_AUTO_MIN_T + 100, 2), jnp.int32)
    st_auto, ys_auto = b.stream(st, xs, 32)  # auto: warm + long → chunked
    assert b._chunked_engines
    st_pe, ys_pe = b.stream(st, xs, 32, chunked=False)
    assert_tree_close(ys_auto, ys_pe, exact=True)
    assert_tree_close(b.query(st_auto), b.query(st_pe), exact=True)


def test_warm_stream_inside_jit_stays_per_element():
    """Traced states cannot take the host-side chunk loop — auto routing
    must quietly stay on the scan path under jit."""
    m = monoids.sum_monoid(jnp.int32)
    b = BatchedSWAG(ALGORITHMS["daba_lite"], m, 12)
    st = b.init(2)
    xs = scalar_vals((40, 2), jnp.int32)

    @jax.jit
    def run(st, xs):
        return b.stream(st, xs, 8)[1]

    assert_tree_close(run(st, xs), b.stream(st, xs, 8, chunked=False)[1], True)


# ---------------------------------------------------------------------------
# ragged last chunk: identity padding, single compilation
# ---------------------------------------------------------------------------


def test_ragged_last_chunk_reuses_one_compilation():
    m = monoids.sum_monoid(jnp.int32)
    eng = ChunkedStream(m, window=8, chunk=16)
    traces = []
    orig = eng._process_chunk_impl

    def counting_impl(carry, xs, mask=None):
        traces.append(jax.tree.leaves(xs)[0].shape)
        return orig(carry, xs, mask)

    eng._jitted_pc = jax.jit(counting_impl)
    xs = scalar_vals((53, 2), jnp.int32)  # 3 full chunks + ragged 5
    ys = eng.stream(xs)
    assert len(traces) == 1, f"expected one trace, got shapes {traces}"
    ref = ChunkedStream(m, window=8, chunk=53).stream(xs)
    assert_tree_close(ys, ref, exact=True)


def test_masked_chunk_positions_are_identity():
    """Masked positions act as monoid identity on both engine paths."""
    for m, mk, exact in [MONOID_CASES["sum_i32"], MONOID_CASES["mean"]]:
        eng = ChunkedStream(m, window=4, chunk=8)
        xs = mk((8, 2))
        mask = jnp.arange(8) < 5
        carry = eng.init_carry(2)
        _, y = eng.process_chunk(carry, xs, mask)
        ref = ChunkedStream(m, window=4, chunk=5).stream(
            jax.tree.map(lambda a: a[:5], xs)
        )
        assert_tree_close(
            jax.tree.map(lambda a: a[:5], y), ref, exact, m.name
        )
