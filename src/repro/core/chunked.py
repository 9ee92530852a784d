"""Chunked streaming engine — bulk sliding-window aggregation (paper §8.2).

Turns the per-element SWAG scan into chunk-at-a-time bulk work, the
throughput counterpart of DABA's latency bound (cf. the authors' follow-up
on efficient bulk evictions/insertions, arXiv 2307.11210):

  * **intra-chunk** window outputs come from ONE dense sliding-window pass
    over the chunk — the Pallas VHGW kernel for scalar elementwise monoids,
    or a generic log-depth ``associative_scan`` VHGW for arbitrary pytree
    monoids;
  * **cross-chunk** boundaries are carried by a per-lane *tail* of suffix
    aggregates of the last ``window - 1`` elements, updated with one suffix
    scan per chunk (the dense analogue of DABA Lite's front list: output =
    Π_front ⊗ Π_back becomes ``y[i] = tail[i] ⊗ prefix[i]``).

Results equal the per-element ``BatchedSWAG.stream`` outputs exactly for
integer monoids and up to combine reassociation (allclose) for floats.

Layouts: streams are ``(T, B)``-leading like ``BatchedSWAG.stream``; the
Pallas kernels internally work on ``(B, T)``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import swag_base
from repro.core.monoids import Monoid
from repro.core.swag_base import (
    chunk_length,
    chunk_suffix_scan,
    suffix_scan,
    tree_index,
)
from repro.kernels.ops_registry import (
    combine_fn,
    identity_for,
    op_for_monoid,
    resolve_interpret,
)
from repro.kernels.sliding_window.kernel import sliding_window_pallas
from repro.kernels.suffix_scan.kernel import suffix_scan_pallas

PyTree = Any


# ---------------------------------------------------------------------------
# Generic (pytree-monoid) VHGW sliding window
# ---------------------------------------------------------------------------


def _axis1_prefix_scan(monoid: Monoid, blocks: PyTree) -> PyTree:
    return jax.lax.associative_scan(monoid.combine, blocks, axis=1)


def _axis1_suffix_scan(monoid: Monoid, blocks: PyTree) -> PyTree:
    # operand-order discipline lives in swag_base.suffix_scan
    return suffix_scan(monoid.combine, blocks, axis=1)


def tree_sliding_window(monoid: Monoid, lifted: PyTree, window: int) -> PyTree:
    """Front-truncated sliding-window fold along axis 0 of a lifted chunk.

    ``out[t] = lifted[max(0, t-window+1)] ⊗ … ⊗ lifted[t]`` — the VHGW
    (two-stacks-in-space) scheme of the Pallas kernel, expressed with
    ``associative_scan`` so it works for ANY pytree monoid: ~3 combines per
    element independent of ``window``, O(log window) depth.  Trailing axes
    (batch, element shape) ride along elementwise.
    """
    C = chunk_length(lifted)
    w = int(window)
    if w <= 1 or C == 0:
        return lifted
    ident = monoid.identity()
    nblk = -(-(C + w) // w)  # blocks of w covering [front pad w] + chunk
    total = nblk * w

    def pad(a, i):
        i = jnp.asarray(i, a.dtype)
        front = jnp.broadcast_to(i, (w,) + a.shape[1:])
        tail = jnp.broadcast_to(i, (total - w - C,) + a.shape[1:])
        return jnp.concatenate([front, a, tail], axis=0)

    padded = jax.tree.map(pad, lifted, ident)
    blocks = jax.tree.map(lambda a: a.reshape((nblk, w) + a.shape[1:]), padded)
    p = _axis1_prefix_scan(monoid, blocks)   # P[j, i] = fold(block_j[0..i])
    s = _axis1_suffix_scan(monoid, blocks)   # S[j, i] = fold(block_j[i..w-1])
    pf = jax.tree.map(lambda a: a.reshape((total,) + a.shape[2:]), p)
    sf = jax.tree.map(lambda a: a.reshape((total,) + a.shape[2:]), s)

    # Window ending at chunk position t covers padded [t+1 .. t+w]:
    # left fragment S[t+1] (identity when t+1 sits on a block boundary —
    # the window is then exactly one block's prefix), right fragment P[t+w].
    idx = jnp.arange(C, dtype=jnp.int32)
    on_boundary = ((idx + 1) % w) == 0
    left = jax.tree.map(
        lambda a, i: jnp.where(
            on_boundary.reshape((C,) + (1,) * (a.ndim - 1)),
            jnp.asarray(i, a.dtype),
            a[idx + 1],
        ),
        sf,
        ident,
    )
    right = jax.tree.map(lambda a: a[idx + w], pf)
    return monoid.combine(left, right)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class ChunkedStream:
    """Chunk-at-a-time count-based sliding-window aggregation over (T, B).

    Usage::

        eng = ChunkedStream(monoid, window=1024, chunk=1024)
        carry = eng.init_carry(batch)
        carry, ys = eng.process_chunk(carry, xs_chunk)   # (C, B) in/out
        ...                                              # or, whole stream:
        ys = eng.stream(xs)                              # (T, B) -> (T, B)

    ``ys[t]`` is the *aggregate* (pre-``lower``) of the last ``window``
    elements ending at t, front-truncated during fill — element-for-element
    what ``BatchedSWAG.stream`` emits, computed ~3 combines/element in bulk
    instead of O(1)-per-element sequential dispatch.

    When the monoid maps onto a registry op (sum/min/max/logsumexp/..., see
    :mod:`repro.kernels.ops_registry`) the intra-chunk passes run on the
    Pallas ``sliding_window``/``suffix_scan`` kernels; any other monoid uses
    the generic ``associative_scan`` path.  The carry is a per-lane tail of
    ``window - 1`` suffix aggregates — the engine never stores raw history —
    and can be initialized cold (identity) or WARM from any live SWAG state
    via ``init_carry(from_state=..., algo=...)`` (the warm-state carry
    protocol, :mod:`repro.core.swag_base`).
    """

    def __init__(
        self,
        monoid: Monoid,
        window: int,
        chunk: Optional[int] = None,
        *,
        use_kernel: bool = True,
        interpret: Optional[bool] = None,
        block_b: int = 8,
        obs: Optional[Any] = None,
    ):
        self.monoid = monoid
        self.window = int(window)
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.chunk = int(chunk) if chunk is not None else max(self.window, 256)
        self.op = op_for_monoid(monoid) if use_kernel else None
        self.interpret = resolve_interpret(interpret)
        self.block_b = block_b
        self._jitted_pc = jax.jit(self._process_chunk_impl)
        self._full_masks: dict = {}
        # obs: repro.obs.registry.ObsConfig — host-side chunk/row counters
        # only; this engine has no jit-visible instrumentation, so disabled
        # vs enabled never changes the traced computation
        self._obs = obs if (obs is not None and obs.enabled) else None
        self._obs_chunks = 0
        self._obs_rows = 0

    # -- timestamped (event-time) mode -------------------------------------

    @staticmethod
    def timestamped(monoid: Monoid, horizon, **kwargs):
        """Event-time counterpart of this engine: ``(ts, x)`` chunks, a time
        ``horizon`` instead of a count window, per-chunk watermark advance,
        and a bounded out-of-order reorder buffer (late-data policies:
        drop / side_output / merge).  Returns a
        :class:`repro.core.event_time.EventTimeChunkedStream`; see that
        module for the watermark and merge-order semantics."""
        from repro.core.event_time import EventTimeChunkedStream

        return EventTimeChunkedStream(monoid, horizon, **kwargs)

    # -- carry ------------------------------------------------------------

    def init_carry(
        self,
        batch: Optional[int] = None,
        *,
        from_state: Optional[PyTree] = None,
        algo=None,
    ) -> PyTree:
        """Tail of suffix aggregates of the last window-1 elements (per lane).

        Cold start (``from_state=None``): identity-filled, so missing history
        combines away exactly (= the front-truncated fill semantics).

        Warm start: pass a *batched* live SWAG state (leading lane axis, as
        built by ``BatchedSWAG.init``) plus its algorithm module, and the
        carry is extracted through the warm-carry protocol
        (:func:`repro.core.swag_base.state_to_carry`) — the stream then
        continues the live window instead of restarting from empty.  Lane
        sizes may be ragged; each lane is front-truncated independently.
        """
        h = self.window - 1
        if from_state is not None:
            if algo is None:
                raise ValueError("init_carry(from_state=...) needs algo=")
            tails = jax.vmap(
                lambda s: swag_base.state_to_carry(
                    algo, self.monoid, s, self.window
                )
            )(from_state)  # (B, h, ...)-leading
            if self.op is not None:
                return tails  # kernel carry layout is (batch, h)
            # generic carry layout is (h, batch, ...)
            return jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), tails)
        if batch is None:
            raise ValueError("init_carry needs batch= (or from_state=)")
        ident = self.monoid.identity()
        if self.op is not None:
            ident = jnp.asarray(ident)
            return jnp.full((batch, h), ident, ident.dtype)
        return jax.tree.map(
            lambda i: jnp.broadcast_to(i, (h, batch) + i.shape).copy(), ident
        )

    # -- one chunk ---------------------------------------------------------

    def process_chunk(self, carry: PyTree, xs: PyTree, mask=None):
        """Consume a (C, B) chunk of raw inputs; returns (carry, (C, B) aggs).

        ``mask`` is an optional (C,) bool array; False positions enter the
        window as the monoid identity (their output rows are meaningless —
        slice them off).  It exists to pad a ragged FINAL chunk up to the
        engine's static chunk length without a fresh jit trace: the returned
        carry treats masked positions as real identity elements, so only mask
        when no further chunks follow.  A full mask is always passed to the
        jitted function so full and padded chunks share one compilation.
        """
        if mask is None:
            mask = self._full_mask(chunk_length(xs))
        if self._obs is not None:
            self._obs_chunks += 1
            self._obs_rows += int(chunk_length(xs))
        return self._jitted_pc(carry, xs, mask)

    def attach_obs(self, registry, *, prefix: str = "repro_chunked"):
        """Register host-side throughput counters with an obs registry
        (rates come from scrape deltas, e.g. in the dashboard)."""
        registry.describe(f"{prefix}_chunks_total", "counter",
                          "process_chunk dispatches")
        registry.describe(f"{prefix}_rows_total", "counter",
                          "chunk rows ingested (incl. ragged-final padding)")

        def collect():
            return {
                f"{prefix}_chunks_total": self._obs_chunks,
                f"{prefix}_rows_total": self._obs_rows,
            }

        registry.register_collector(collect)
        return collect

    def chunk_fn(self, carry: PyTree, xs: PyTree, mask=None):
        """Unjitted :meth:`process_chunk` body — pure, for composing into a
        caller's own ``jit`` (e.g. the telemetry layer's fused observe)."""
        return self._process_chunk_impl(carry, xs, mask)

    def _full_mask(self, C: int):
        m = self._full_masks.get(C)
        if m is None:
            m = self._full_masks[C] = jnp.ones((C,), bool)
        return m

    def _process_chunk_impl(self, carry, xs, mask=None):
        if self.op is not None:
            return self._chunk_kernel(carry, xs, mask)
        return self._chunk_generic(carry, xs, mask)

    def _chunk_kernel(self, tail, xs, mask=None):
        m = self.monoid
        lifted = jax.vmap(jax.vmap(m.lift))(xs)  # (C, B) scalar Agg
        if lifted.ndim != 2:
            raise ValueError(
                f"kernel path needs scalar aggregates, got shape {lifted.shape}"
            )
        if mask is not None:
            ident = jnp.asarray(identity_for(self.op, lifted.dtype), lifted.dtype)
            lifted = jnp.where(mask[:, None], lifted, ident)
        x = lifted.T  # (B, C) for the kernels
        C = x.shape[1]
        w, h = self.window, min(self.window - 1, x.shape[1])
        comb = combine_fn(self.op)
        y = sliding_window_pallas(
            x, window=w, op=self.op, block_b=self.block_b, interpret=self.interpret
        )
        if h > 0:
            y = y.at[:, :h].set(comb(tail[:, :h], y[:, :h]))
        if w > 1:
            ss = suffix_scan_pallas(
                x, op=self.op, block_b=self.block_b, interpret=self.interpret
            )
            if C >= w - 1:
                tail = ss[:, C - (w - 1):]
            else:
                # shift the old tail down by C and absorb the chunk total
                tail = jnp.concatenate([comb(tail[:, C:], ss[:, :1]), ss], axis=1)
        return tail, y.T

    def _chunk_generic(self, tail, xs, mask=None):
        m = self.monoid
        lifted = jax.vmap(jax.vmap(m.lift))(xs)  # (C, B, ...) Agg pytree
        if mask is not None:
            ident = m.identity()
            lifted = jax.tree.map(
                lambda a, i: jnp.where(
                    mask.reshape((-1,) + (1,) * (a.ndim - 1)),
                    a,
                    jnp.asarray(i, a.dtype),
                ),
                lifted,
                ident,
            )
        C = chunk_length(lifted)
        w, h = self.window, min(self.window - 1, chunk_length(lifted))
        y = tree_sliding_window(m, lifted, w)
        if h > 0:
            fixed = m.combine(
                jax.tree.map(lambda a: a[:h], tail),
                jax.tree.map(lambda a: a[:h], y),
            )
            y = jax.tree.map(lambda a, f: a.at[:h].set(f), y, fixed)
        if w > 1:
            ss = chunk_suffix_scan(m, lifted)
            if C >= w - 1:
                tail = jax.tree.map(lambda a: a[C - (w - 1):], ss)
            else:
                total = tree_index(ss, 0)
                shifted = jax.vmap(m.combine, in_axes=(0, None))(
                    jax.tree.map(lambda a: a[C:], tail), total
                )
                tail = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], axis=0), shifted, ss
                )
        return tail, y

    # -- whole stream ------------------------------------------------------

    def stream(self, xs: PyTree, *, carry: Optional[PyTree] = None) -> PyTree:
        """Aggregate a whole (T, B) stream chunk-by-chunk; returns (T, B) aggs.

        ``carry`` continues from an existing tail (see :meth:`init_carry`'s
        ``from_state=`` path for warm windows); default is a cold start.  A
        ragged last chunk is padded to ``self.chunk`` with the monoid
        identity under a mask, so every chunk — ragged included — reuses the
        single ``process_chunk`` compilation.
        """
        T = chunk_length(xs)
        batch = jax.tree.leaves(xs)[0].shape[1]
        if T == 0:  # match the per-element scan: well-formed empty (0, B) aggs
            return jax.vmap(jax.vmap(self.monoid.lift))(xs)
        if carry is None:
            carry = self.init_carry(batch)
        ys = []
        for lo in range(0, T, self.chunk):
            hi = min(lo + self.chunk, T)
            piece = jax.tree.map(lambda a: a[lo:hi], xs)
            if hi - lo < self.chunk:  # final ragged chunk: pad + mask
                pad = self.chunk - (hi - lo)
                piece = jax.tree.map(
                    lambda a: jnp.concatenate(
                        [a, jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])], 0
                    ),
                    piece,
                )
                mask = jnp.arange(self.chunk) < (hi - lo)
                carry, y = self.process_chunk(carry, piece, mask)
                y = jax.tree.map(lambda a: a[: hi - lo], y)
            else:
                carry, y = self.process_chunk(carry, piece)
            ys.append(y)
        return jax.tree.map(lambda *parts: jnp.concatenate(parts, axis=0), *ys)
