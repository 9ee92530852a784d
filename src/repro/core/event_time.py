"""Event-time windowing: timestamped streams, watermarks, bulk out-of-order
aggregation (cf. the authors' follow-ups arXiv 1810.11308 "Sub-O(log n)
Out-of-Order Sliding-Window Aggregation" and arXiv 2307.11210 "Out-of-Order
SWAG with Efficient Bulk Evictions and Insertions").

The count-based engines (:mod:`repro.core.chunked`, the per-element SWAG
algorithms) define a window as "the last N elements".  Production streams are
*event-time*: every element carries a timestamp, the window is a time span
(``horizon``), elements arrive slightly out of order, and eviction is driven
by a **watermark** — a lower bound on all future event times.  This module
threads those semantics through the bulk-op machinery of
:mod:`repro.core.swag_base`:

  * :class:`TimestampedWindow` — the per-element protocol: any SWAG algorithm
    plus a parallel timestamp queue; ``advance(watermark)`` turns watermark
    movement into ONE ``evict_bulk`` of every expired element.
  * :class:`EventTimeChunkedStream` — the bulk engine: ``(ts, x)`` chunks,
    per-chunk watermark advance, a bounded out-of-order reorder buffer that
    stable-sorts/merges late arrivals into the aggregate, and per-released-
    element window outputs computed with log-depth vectorized scans.
  * :func:`in_order_reference` — the eager oracle the tests hold both
    engines to.

Watermark / late-data semantics
-------------------------------

The engine tracks ``max_ts``, the largest event time seen so far, and sets
the watermark ``wm = max_ts - slack`` (monotone, per-chunk advance).  An
element is **released** — merged into the window, its output emitted — once
``ts <= wm``; until then it waits in the reorder buffer.  An element is
**late** when it arrives with ``ts`` *below* the watermark that was already
published before its chunk.  Late policy:

  * ``"drop"``        — discard, count in ``n_dropped``;
  * ``"side_output"`` — discard from the window, but report the rows so the
    caller can reroute them (:class:`EventTimeResult.late_rows`);
  * ``"merge"``       — merge into the window at the correct event-time
    position as long as the element is still inside the horizon
    (``ts > wm - horizon``; older is dropped).  Future outputs are exact;
    outputs already emitted are not rewritten, and the merged element's OWN
    output may miss in-window peers older than ``wm - horizon`` that were
    already evicted.

Whenever every element's lateness is within ``slack`` (``ts >= running max
of previous chunks - slack``), nothing is ever late, and the concatenated
released outputs equal the in-order per-element reference of the
*timestamp-sorted* stream — bit-exactly for integer monoids (see
tests/test_event_time.py).

Non-commutative merge-order invariant
-------------------------------------

Everything is ordered by ``(event time, arrival order)``: the reorder buffer
is kept time-sorted, chunks are stable-sorted on entry (buffer entries
precede same-timestamp chunk entries; chunk entries keep arrival order on
ties), and released elements stable-merge *after* same-timestamp window
contents.  This is exactly the order a per-element scan of the stable-sorted
stream would use — the FiBA papers' in-order merge discipline — so
non-commutative monoids (argmax tie-breaks, m4 first/last, affine
composition) stay exact: no combine ever sees its operands swapped.

The merge/insert machinery that implements this rule now lives in
:mod:`repro.core.ooo_index` (the vectorized finger-style tail index), and
the engine is **disorder-adaptive**: a per-chunk ``lax.cond`` takes a fast
branch — no sort, no searchsorted merge, released rows append after the
window — whenever the chunk appends at the frontier (out-of-order distance
0), and otherwise stable-sorts only the trailing (buffer ++ chunk) region
and rank-merges it in, the 1810.11308 / 2307.11210 cost shape: work scales
with the out-of-order distance, never the window.  Both branches emit
byte-identical layouts, so outputs are bit-exact across branches (see
README "Out-of-order hot path").

The flip invariant (constant-combine bulk outputs)
--------------------------------------------------

THIS is the one place the sweep contract is stated; README "The keyed hot
path" and :mod:`repro.core.keyed` cross-reference it.

Per-released-element outputs cover a *variable-width* span (everything with
``ts' > ts - horizon``), which a fixed-count sliding pass cannot produce.
Because releases are processed in event order, the query set is **monotone**:
both the span starts and the span ends are non-decreasing over the merged
window-plus-released array.  That is exactly the two-stacks regime: partition
the array at *flip boundaries* chosen so every query's start lands in the
partition cell *before* (or at the start of) the cell holding its end; then

    out[q] = suffix_scan_within_cell[start_q] ⊗ prefix_scan_from_cell_start[end_q]

— one segmented suffix scan + one segmented prefix scan + one combine per
query: a worst-case-constant number of ⊗ per swept element, for ANY monoid
(:func:`flip_range_fold`; the retired O(log(W+C)) doubling table survives as
:func:`range_fold`, kept as the bit-exactness reference).  Invertible
*commutative* monoids — sum, count, mean, … — skip even that and use one
prefix scan plus ``inverse_front`` (:func:`range_fold_invertible`).

**Operand-order rule (non-commutative monoids).**  Every combine keeps the
OLDER operand on the left: the suffix-scan term covers ``[start_q, flip)``
and therefore sits LEFT of the prefix-scan term covering ``[flip, end_q]``;
inside :func:`seg_suffix_scan` the array is flipped, so its pair operator
swaps its operands back (``combine(newer-flipped b, a)``), while
:func:`seg_prefix_scan` combines in natural order.  No combine anywhere in
the sweep ever sees its operands swapped — argmax tie-breaks, m4
first/last, and affine composition stay bit-exact.

Timestamps are any real dtype; values strictly inside (``TS_MIN``,
``TS_MAX``) of that dtype (the extremes are the engine's pad sentinels).
Lanes: like :class:`~repro.core.chunked.ChunkedStream`, streams are
``(T, B)``-leading with ONE shared timestamp per row.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ooo_index, swag_base
from repro.core.monoids import Monoid
from repro.core.swag_base import chunk_length, tree_index
from repro.obs import counters as obs_counters

PyTree = Any


# ---------------------------------------------------------------------------
# Sentinels
# ---------------------------------------------------------------------------


def ts_limits(dtype) -> tuple:
    """(TS_MIN, TS_MAX) pad sentinels for a timestamp dtype.  Real event
    times must lie strictly between them."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        info = jnp.finfo(dtype)
    else:
        info = jnp.iinfo(dtype)
    return info.min, info.max


def _bc(mask, leaf):
    """Broadcast a (L,) mask over a (L, ...) leaf."""
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))


def _mask_tree(tree: PyTree, mask, ident: PyTree) -> PyTree:
    """Leaves where ``mask`` is False become the (broadcast) identity."""
    return jax.tree.map(
        lambda a, i: jnp.where(_bc(mask, a), a, jnp.asarray(i, a.dtype)),
        tree,
        ident,
    )


def _take0(tree: PyTree, idx) -> PyTree:
    return jax.tree.map(lambda a: a[idx], tree)


def _where_rows(mask, a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: jnp.where(_bc(mask, x), x, y), a, b)


def fold_axis0(monoid: Monoid, tree_arr: PyTree) -> PyTree:
    """Ordered log-depth fold of a (L, ...) stack: x_0 ⊗ x_1 ⊗ … ⊗ x_{L-1}.

    Pairs adjacent rows (older operand left), padding an odd tail with the
    identity — safe for non-commutative monoids (exactness test:
    tests/test_event_time.py::test_fold_axis0_ordered).  Deliberately NOT
    ``swag_base.chunk_fold``: that computes a full suffix scan (L log L
    combines) to read one entry, while this is the telemetry read path —
    ``window_fold`` runs per observation — and needs only L combines.
    """
    ident = monoid.identity()
    n = chunk_length(tree_arr)
    if n == 0:
        return ident
    while n > 1:
        if n % 2:
            tree_arr = jax.tree.map(
                lambda a, i: jnp.concatenate(
                    [a, jnp.broadcast_to(jnp.asarray(i, a.dtype), (1,) + a.shape[1:])],
                    axis=0,
                ),
                tree_arr,
                ident,
            )
            n += 1
        tree_arr = monoid.combine(
            jax.tree.map(lambda a: a[0::2], tree_arr),
            jax.tree.map(lambda a: a[1::2], tree_arr),
        )
        n //= 2
    return tree_index(tree_arr, 0)


# ---------------------------------------------------------------------------
# Variable-span range folds (the bulk event-time window primitive)
# ---------------------------------------------------------------------------


def range_fold(monoid: Monoid, arr: PyTree, starts, ends) -> PyTree:
    """``out[q] = arr[starts[q]] ⊗ … ⊗ arr[ends[q]]`` for every query q.

    Doubling table + binary span decomposition, left-to-right (exact for
    non-commutative monoids; see module docstring).  ``arr`` is an (M, ...)
    stack; ``starts``/``ends`` are (Q,) int32; an empty span
    (``ends < starts``) yields the identity.  O(M log M) combines to build
    the table, O(log M) per query, everything vectorized.
    """
    ident = monoid.identity()
    M = chunk_length(arr)
    levels = [arr]
    span = 1
    while span < M:
        prev = levels[-1]
        shifted = jax.tree.map(
            lambda a, i: jnp.concatenate(
                [
                    a[span:],
                    jnp.broadcast_to(
                        jnp.asarray(i, a.dtype), (min(span, M),) + a.shape[1:]
                    ),
                ],
                axis=0,
            ),
            prev,
            ident,
        )
        levels.append(monoid.combine(prev, shifted))
        span *= 2

    starts = jnp.asarray(starts, jnp.int32)
    ends = jnp.asarray(ends, jnp.int32)
    length = jnp.maximum(ends - starts + 1, 0)
    acc = jax.tree.map(
        lambda a, i: jnp.broadcast_to(
            jnp.asarray(i, a.dtype), starts.shape + a.shape[1:]
        ),
        arr,
        ident,
    )
    pos = starts
    for k in reversed(range(len(levels))):
        take = ((length >> k) & 1).astype(bool)
        vals = _take0(levels[k], jnp.clip(pos, 0, M - 1))
        acc = _where_rows(~take, acc, monoid.combine(acc, vals))
        pos = pos + jnp.where(take, jnp.int32(1 << k), jnp.int32(0))
    return acc


def range_fold_invertible(monoid: Monoid, arr: PyTree, starts, ends) -> PyTree:
    """Range folds via one prefix scan + ``inverse_front`` — O(1) combines
    per query.  Requires an invertible COMMUTATIVE monoid (the inverse
    removes a whole prefix, which is only order-safe when ⊗ commutes)."""
    ident = monoid.identity()
    M = chunk_length(arr)
    pref = jax.lax.associative_scan(monoid.combine, arr, axis=0)
    starts = jnp.asarray(starts, jnp.int32)
    ends = jnp.asarray(ends, jnp.int32)
    at_end = _take0(pref, jnp.clip(ends, 0, M - 1))
    before = _take0(pref, jnp.clip(starts - 1, 0, M - 1))
    sliced = monoid.inverse_front(at_end, before)
    full = _where_rows(starts > 0, sliced, at_end)
    empty_or_pad = (ends < starts) | (ends < 0)
    identity_rows = jax.tree.map(
        lambda a, i: jnp.broadcast_to(jnp.asarray(i, a.dtype), a.shape), full, ident
    )
    return _where_rows(empty_or_pad, identity_rows, full)


# Host-side ⊗ counters for the flip sweeps (engines built with
# ``instrument_combines=True``): every combine in an instrumented sweep
# bumps its engine's counter by the number of element-rows it touched — the
# regression tests assert combines-per-swept-element stays FLAT as the
# window grows (the constant-combine claim, measured at runtime).  The
# counters now live in :mod:`repro.obs.counters` (one home for the
# effects-barrier-before-read rule); ``COMBINE_COUNTS`` is a thin
# deprecated alias — the dict surface still works, and barriered reads
# should go through ``obs_counters.combines.read()``.
COMBINE_COUNTS = obs_counters.combines


def reset_combine_counts() -> None:
    obs_counters.combines.reset()


def _count_combines(key: str, n: int) -> None:
    obs_counters.combines.bump(key, n)


def _count_release(key: str) -> None:
    obs_counters.releases.bump(key, 1)


# ring length of the per-chunk out-of-order distance gauge in the engine
# state: obs scrapes report max/p95 over the last OOO_RING chunks
OOO_RING = 32


def counting_combines(monoid: Monoid, key: str) -> Monoid:
    """``monoid`` with a combine that bumps the ``obs.counters.combines``
    group (key = engine name) by the static leading-axis length of its
    operands at every runtime invocation (a ``jax.debug.callback``, so
    jitted executions are counted too)."""

    def combine(a, b):
        n = int(chunk_length(a))
        jax.debug.callback(lambda key=key, n=n: _count_combines(key, n))
        return monoid.combine(a, b)

    return dataclasses.replace(
        monoid, name=monoid.name + "#combcount", combine=combine
    )


# ---------------------------------------------------------------------------
# Segmented scans (the flip-sweep building blocks)
# ---------------------------------------------------------------------------


def seg_suffix_scan(monoid: Monoid, end_flags, lifted: PyTree) -> PyTree:
    """Suffix scan that resets at segment ends: ``out[i] = x_i ⊗ … ⊗ x_e(i)``
    where ``e(i)`` is the last index of i's segment (``end_flags[e] = True``).

    Built from the classic segmented-scan pair operator on the flipped
    array with swapped combine operands, keeping the older operand LEFT
    (the operand-order rule in the module docstring) — exact for
    non-commutative monoids.
    """
    flags = jnp.flip(jnp.asarray(end_flags, bool))
    vals = jax.tree.map(lambda a: jnp.flip(a, 0), lifted)

    def comb(a, b):
        fa, va = a
        fb, vb = b
        merged = monoid.combine(vb, va)  # flipped order: b is OLDER
        v = jax.tree.map(
            lambda mv, bv: jnp.where(_bc(fb, bv), bv, mv), merged, vb
        )
        return (fa | fb, v)

    _, out = jax.lax.associative_scan(comb, (flags, vals), axis=0)
    return jax.tree.map(lambda a: jnp.flip(a, 0), out)


def seg_prefix_scan(monoid: Monoid, start_flags, lifted: PyTree) -> PyTree:
    """Prefix scan that resets at segment starts: ``out[i] = x_s(i) ⊗ … ⊗ x_i``
    where ``s(i)`` is the last index ≤ i with ``start_flags`` True (0 when
    none).  Natural-order pair operator, older operand LEFT — the mirror of
    :func:`seg_suffix_scan` and the second half of every flip sweep."""
    flags = jnp.asarray(start_flags, bool)

    def comb(a, b):
        fa, va = a
        fb, vb = b
        merged = monoid.combine(va, vb)  # a is OLDER: left
        v = jax.tree.map(
            lambda mv, bv: jnp.where(_bc(fb, bv), bv, mv), merged, vb
        )
        return (fa | fb, v)

    _, out = jax.lax.associative_scan(comb, (flags, lifted), axis=0)
    return out


def flip_range_fold(monoid: Monoid, arr: PyTree, starts, ends, *,
                    instrument: Optional[str] = None) -> PyTree:
    """:func:`range_fold` for MONOTONE query sets in O(1) combines/element.

    Requires ``starts`` non-decreasing and ``ends`` STRICTLY increasing (the
    flip invariant — see the module docstring; violating it silently returns
    wrong folds: two same-end queries with different starts cannot share one
    flip cell).  Released merge positions satisfy both by construction.
    Flip boundaries are the orbit of ``hop(b) = max(b+1, first i whose
    per-position window start ≥ b)`` from 0, marked by gather-only binary
    lifting (O(M log M) *integer* work, zero ⊗, no scatters — scatters
    lower to sequential loops on CPU and were ~40× slower); outputs are one
    segmented suffix scan + one segmented prefix scan + one combine per
    query.  Empty spans
    (``ends < starts``) yield the identity.  ``instrument`` names a
    ``COMBINE_COUNTS`` key to bump per runtime combine.
    """
    ident = monoid.identity()
    m = counting_combines(monoid, instrument) if instrument else monoid
    M = int(chunk_length(arr))
    starts = jnp.asarray(starts, jnp.int32)
    ends = jnp.asarray(ends, jnp.int32)
    Q = int(starts.shape[0])
    if M == 0 or Q == 0:
        return jax.tree.map(
            lambda a, i: jnp.broadcast_to(
                jnp.asarray(i, a.dtype), (Q,) + a.shape[1:]
            ),
            arr,
            ident,
        )
    idx = jnp.arange(M, dtype=jnp.int32)

    # Per-position window start: the smallest start among queries ending at
    # or after i (monotone), clamped to ≤ i so positions no query ends at
    # never force a boundary of their own.
    qi = jnp.searchsorted(ends, idx, side="left").astype(jnp.int32)
    sbar = jnp.where(qi >= Q, M, starts[jnp.clip(qi, 0, Q - 1)])
    s_pos = jnp.clip(jnp.minimum(sbar, idx), 0, M)

    # hop(b) = max(b+1, first i with s_pos[i] >= b); boundaries = orbit of 0.
    # For every query q with end in cell [B_m, B_{m+1}): B_{m-1} <= start_q
    # <= B_m.  Binary lifting: levels[d] = hop^(2^d); a greedy descent from 0
    # yields, for each position i, the largest orbit element <= i (every step
    # count is a sum of powers of two) — i is a boundary iff that is i itself.
    bpos = jnp.arange(M + 1, dtype=jnp.int32)
    first_ge = jnp.searchsorted(s_pos, bpos, side="left").astype(jnp.int32)
    hop = jnp.minimum(jnp.maximum(bpos + 1, first_ge), M)
    levels = [hop]
    for _ in range(max(1, math.ceil(math.log2(M + 1))) - 1):
        levels.append(levels[-1][levels[-1]])
    cur = jnp.zeros((M + 1,), jnp.int32)
    for lv in reversed(levels):
        nxt = lv[cur]
        cur = jnp.where(nxt <= bpos, nxt, cur)
    mark = cur == bpos

    start_flags = mark[:M]
    end_flags = mark[1:] | (idx == M - 1)
    cellstart = jax.lax.associative_scan(
        jnp.maximum, jnp.where(start_flags, idx, 0)
    )
    bpref = seg_prefix_scan(m, start_flags, arr)
    bsuf = seg_suffix_scan(m, end_flags, arr)

    e_c = jnp.clip(ends, 0, M - 1)
    right = _take0(bpref, e_c)  # [cellstart[e], e]
    left = _take0(bsuf, jnp.clip(starts, 0, M - 1))  # [s, its cell end]
    both = m.combine(left, right)  # older operand LEFT
    out = _where_rows(starts >= cellstart[e_c], right, both)
    identity_rows = jax.tree.map(
        lambda a, i: jnp.broadcast_to(jnp.asarray(i, a.dtype), a.shape),
        out,
        ident,
    )
    return _where_rows((ends < starts) | (ends < 0), identity_rows, out)


# ---------------------------------------------------------------------------
# Per-element protocol
# ---------------------------------------------------------------------------


class TimestampedWindow:
    """Event-time sliding window over any SWAG algorithm (per-element).

    Wraps ``algo.init/insert/evict/query`` with a parallel timestamp queue:
    the window holds every element with ``ts' > newest_watermark - horizon``.
    ``insert`` requires event-time order (out-of-order ingestion is
    :class:`EventTimeChunkedStream`'s job); :meth:`advance` turns a watermark
    movement into ONE :func:`repro.core.swag_base.evict_bulk` call covering
    every expired element — the paper's worst-case O(1) per-evict cost times
    exactly the number of expirations, with a single dispatch.
    """

    def __init__(self, algo, monoid: Monoid, horizon, capacity: int):
        self.algo = algo
        self.monoid = monoid
        self.horizon = horizon
        self.capacity = capacity
        self.state = algo.init(monoid, capacity)
        self._ts: collections.deque = collections.deque()
        self.watermark: Optional[float] = None

    def insert(self, ts, value) -> None:
        if self.watermark is not None and ts < self.watermark:
            raise ValueError(
                f"TimestampedWindow.insert needs event-time order (got {ts} "
                f"below the watermark {self.watermark}); use "
                f"EventTimeChunkedStream for out-of-order streams"
            )
        self.state = self.algo.insert(self.monoid, self.state, value)
        self._ts.append(ts)
        self.advance(ts)

    def advance(self, watermark) -> int:
        """Advance the watermark; bulk-evict expired elements.  Returns the
        number evicted."""
        if self.watermark is not None:
            watermark = max(watermark, self.watermark)
        self.watermark = watermark
        k = 0
        thr = watermark - self.horizon
        while self._ts and self._ts[0] <= thr:
            self._ts.popleft()
            k += 1
        if k:
            self.state = swag_base.evict_bulk(self.algo, self.monoid, self.state, k)
        return k

    def query(self):
        return self.algo.query(self.monoid, self.state)

    def lowered_query(self):
        return self.monoid.lower(self.query())

    def size(self) -> int:
        return len(self._ts)

    def __len__(self) -> int:
        return self.size()


# ---------------------------------------------------------------------------
# The bulk engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EventTimeResult:
    """Compacted output of :meth:`EventTimeChunkedStream.stream` (host).

    ``ts``/``ys``: released event times (event order) and the matching
    (R, B, ...) window aggregates (pre-``lower``).  ``late_rows``: arrival
    indices of every element that arrived below the watermark, under ANY
    policy — check ``n_dropped`` to tell excluded rows from merged ones
    (``"merge"`` flags late rows here but still folds the in-horizon ones
    into the window).  ``state``: the final engine state.
    """

    ts: np.ndarray
    ys: Any
    late_rows: np.ndarray
    n_late: int
    n_dropped: int
    state: Any


class EventTimeChunkedStream:
    """Chunk-at-a-time event-time sliding-window aggregation over (T, B).

    Usage::

        eng = EventTimeChunkedStream(monoid, horizon=60.0, slack=5.0)
        state = eng.init_state(batch)
        state, out = eng.process_chunk(state, ts_chunk, xs_chunk)
        ...
        res = eng.stream(ts, xs)      # whole stream + flush, compacted

    Per chunk: watermark advance, then the **disorder-adaptive release
    path** (:mod:`repro.core.ooo_index`) — a ``lax.cond`` tests whether the
    masked chunk appends at the frontier (non-decreasing, everything at or
    above the previous ``max_ts``); if so (out-of-order distance 0, the
    steady state of an in-order stream) the sorted pending run is ONE
    compacting gather and released rows concatenate after the window with
    no sort and no searchsorted merge, else the trailing (buffer ++ chunk)
    region — never the window — is stable time-sorted and released rows
    rank-merge into the live window.  Then per-released-element window
    outputs via the constant-combine :func:`flip_range_fold` sweep (or the
    invertible-commutative prefix-scan fast path), and a watermark-driven
    bulk eviction of expired window entries (a contiguous slice of the
    merged array — no re-sort).
    All shapes are static — full and (mask-padded) ragged chunks share one
    compilation, mirroring :class:`repro.core.chunked.ChunkedStream`.
    ``instrument_release=True`` counts the branch taken per chunk in
    ``repro.obs.counters.releases`` (barrier before reading: use
    ``.read()``); the per-chunk measured out-of-order distance rides in the
    state (``ooo_recent``) and surfaces as the ``ooo_distance`` obs gauges.

    Capacities (static): ``capacity`` bounds the number of live in-horizon
    elements (overflow loses the OLDEST window entries), ``buffer`` bounds
    the reorder buffer (overflow loses the NEWEST pending arrivals — the
    time-sorted prefix closest to release is kept).  Either overflow bumps
    ``state["n_overflow"]`` (checked — with a raise — by :meth:`stream`;
    other callers should poll the counter).
    """

    def __init__(
        self,
        monoid: Monoid,
        horizon,
        *,
        slack=0,
        chunk: int = 256,
        capacity: int = 1024,
        buffer: Optional[int] = None,
        late_policy: str = "drop",
        ts_dtype=jnp.float32,
        use_inverse: Optional[bool] = None,
        instrument_combines: bool = False,
        instrument_release: bool = False,
    ):
        if late_policy not in ("drop", "side_output", "merge"):
            raise ValueError(f"unknown late_policy {late_policy!r}")
        self.monoid = monoid
        self.chunk = int(chunk)
        self.capacity = int(capacity)
        self.buffer = int(buffer) if buffer is not None else self.chunk
        self.late_policy = late_policy
        self.ts_dtype = jnp.dtype(ts_dtype)
        tmin, tmax = ts_limits(self.ts_dtype)
        self._tmin = jnp.asarray(tmin, self.ts_dtype)
        self._tmax = jnp.asarray(tmax, self.ts_dtype)
        self.horizon = jnp.asarray(horizon, self.ts_dtype)
        self.slack = jnp.asarray(slack, self.ts_dtype)
        if use_inverse is None:
            use_inverse = monoid.invertible and monoid.commutative
        self._use_inverse = use_inverse
        self.instrument_combines = bool(instrument_combines)
        self.instrument_release = bool(instrument_release)
        self._jitted = {}  # (C, with_outputs, path) -> jitted impl
        self._scan_jitted = {}  # (T, n_full, path) -> jitted whole-stream scan
        self._full_masks: dict = {}

    # -- state -------------------------------------------------------------

    def init_state(self, batch: int) -> PyTree:
        ident = self.monoid.identity()
        W, K = self.capacity, self.buffer

        def fill(n):
            return jax.tree.map(
                lambda i: jnp.broadcast_to(
                    jnp.asarray(i), (n, batch) + jnp.asarray(i).shape
                ).copy(),
                ident,
            )

        zero = jnp.zeros((), jnp.int32)
        return {
            "win_ts": jnp.full((W,), self._tmin, self.ts_dtype),
            "win_agg": fill(W),
            "buf_ts": jnp.full((K,), self._tmax, self.ts_dtype),
            "buf_agg": fill(K),
            "wm": self._tmin,
            "max_ts": self._tmin,
            "n_late": zero,
            "n_dropped": zero,
            "n_overflow": zero,
            "ooo_recent": jnp.zeros((OOO_RING,), jnp.int32),
        }

    def window_fold(self, state: PyTree) -> PyTree:
        """Aggregate of the live window (pads are identities): (B, ...)."""
        return fold_axis0(self.monoid, state["win_agg"])

    # -- observability -----------------------------------------------------

    def obs_metrics(self, state: PyTree, now=None) -> dict:
        """Engine health as DEVICE scalars — no host sync here; the obs
        registry batches the transfer at scrape time.

        ``watermark_lag`` is ``now - wm`` when the caller supplies a
        processing-time "now" in event-time units, else the engine-internal
        ``max_ts - wm`` (= ``slack`` in steady state, less before the first
        chunk fills it).
        """
        wm, max_ts = state["wm"], state["max_ts"]
        lag = (now - wm) if now is not None else (max_ts - wm)
        return {
            "watermark": wm,
            "watermark_lag": lag,
            "buffer_occupancy":
                (state["buf_ts"] < self._tmax).sum(dtype=jnp.int32),
            "window_occupancy":
                (state["win_ts"] > self._tmin).sum(dtype=jnp.int32),
            "late_total": state["n_late"],
            "dropped_total": state["n_dropped"],
            "overflow_total": state["n_overflow"],
            "ooo_distance_max": jnp.max(state["ooo_recent"]),
            "ooo_distance_p95": jnp.percentile(
                state["ooo_recent"].astype(jnp.float32), 95.0
            ),
        }

    def attach_obs(self, registry, get_state, *, prefix: str = "repro_eventtime"):
        """Register a scrape collector: ``get_state()`` must return the
        engine's CURRENT state (host-owned, e.g. the variable the caller
        threads through :meth:`process_chunk` — this engine does not donate,
        so the reference stays valid)."""
        names = {
            "watermark": (f"{prefix}_watermark", "gauge",
                          "event-time watermark (max_ts - slack)"),
            "watermark_lag": (f"{prefix}_watermark_lag", "gauge",
                              "event-time distance max_ts - wm"),
            "buffer_occupancy": (f"{prefix}_reorder_buffer_occupancy", "gauge",
                                 "live entries waiting in the reorder buffer"),
            "window_occupancy": (f"{prefix}_window_occupancy", "gauge",
                                 "live entries inside the horizon window"),
            "late_total": (f"{prefix}_late_total", "counter",
                           "elements that arrived below the published watermark"),
            "dropped_total": (f"{prefix}_dropped_total", "counter",
                              "late elements discarded by the drop policy"),
            "overflow_total": (f"{prefix}_overflow_total", "counter",
                               "elements lost to reorder-buffer/window overflow"),
            "ooo_distance_max": (
                f"{prefix}_ooo_distance_max", "gauge",
                f"max measured out-of-order distance over the last "
                f"{OOO_RING} chunks"),
            "ooo_distance_p95": (
                f"{prefix}_ooo_distance_p95", "gauge",
                f"p95 measured out-of-order distance over the last "
                f"{OOO_RING} chunks"),
        }
        for key, (series, typ, help) in names.items():
            registry.describe(series, typ, help)

        def collect():
            metrics = self.obs_metrics(get_state())
            return {names[k][0]: v for k, v in metrics.items()}

        registry.register_collector(collect)
        return collect

    # -- one chunk ---------------------------------------------------------

    def process_chunk(self, state, ts, xs, mask=None, *, final=False,
                      with_outputs: bool = True, path: Optional[str] = None):
        """Consume a chunk: ``ts`` (C,), ``xs`` (C, B, ...) raw inputs.

        ``mask`` (C,) pads a ragged final chunk (False rows are ignored
        entirely).  ``final=True`` pushes the watermark to +∞, draining the
        reorder buffer (end of stream).  ``with_outputs=False`` skips the
        per-released-element outputs (window/buffer upkeep only — the
        telemetry read path).  Returns ``(state, out)`` with ``out`` a dict:
        ``ts``/``ys`` (P = buffer+C rows, ``mask`` selects the released
        prefix, event order) and ``late`` (C,) late-arrival flags.

        ``path`` pins the release branch STATICALLY (its own jit cache
        entry): ``None`` (default) traces the runtime ``lax.cond``;
        ``"slow"`` always sorts (correct for any chunk); ``"fast"``
        compiles the branch-free in-order program — the caller GUARANTEES
        the chunk appends at the frontier (:meth:`stream` proves this on
        the host from the full timestamp array; an unproven "fast" on a
        disordered chunk silently corrupts the window).  XLA:CPU charges a
        conditional in this program shape ~400 us/chunk in lost fusion, so
        the static variants are the hot path.
        """
        C = int(jnp.shape(jnp.asarray(ts))[0])
        if mask is None:
            mask = self._full_mask(C)
        key = (C, bool(with_outputs), path)
        fn = self._jitted.get(key)
        if fn is None:
            fn = self._jitted[key] = jax.jit(
                lambda st, t, x, mk, fin: self._process_impl(
                    st, t, x, mk, fin, with_outputs, path
                )
            )
        return fn(state, ts, xs, mask, jnp.asarray(final, bool))

    def chunk_fn(self, state, ts, xs, mask=None, *, final=False,
                 with_outputs: bool = True, path: Optional[str] = None):
        """Unjitted :meth:`process_chunk` body — pure, for composing into a
        caller's own ``jit`` (the telemetry layer's fused observe)."""
        C = int(jnp.shape(jnp.asarray(ts))[0])
        if mask is None:
            mask = self._full_mask(C)
        return self._process_impl(
            state, ts, xs, mask, jnp.asarray(final, bool), with_outputs, path
        )

    def flush(self, state, example_xs):
        """Drain the reorder buffer (watermark → +∞): every pending element
        is released and the resulting window is fully evicted — terminal,
        for end-of-stream.  ``example_xs`` is any one-row (1, B, ...) input
        tree (values ignored — fully masked); it only fixes the traced
        shapes."""
        ts = jnp.zeros((1,), self.ts_dtype)
        mask = jnp.zeros((1,), bool)
        row = jax.tree.map(lambda a: a[:1], example_xs)
        # a fully-masked chunk is trivially at the frontier (every ts_in row
        # is the TS_MAX sentinel), so the drain always takes the fast path
        return self.process_chunk(state, ts, row, mask, final=True,
                                  path="fast")

    def _full_mask(self, C: int):
        m = self._full_masks.get(C)
        if m is None:
            m = self._full_masks[C] = jnp.ones((C,), bool)
        return m

    def _stream_scan(self, T: int, n_full: int, path: str):
        """Jitted ``lax.scan`` over a stream's full-chunk prefix: ONE
        dispatch for ``n_full`` chunks, every chunk on the statically
        resolved release branch (see :meth:`stream` — the per-chunk python
        dispatch otherwise dominates the fast path).  Outputs come back
        stacked with an (n_full,) leading axis."""
        key = (T, n_full, path)
        fn = self._scan_jitted.get(key)
        if fn is None:
            C = self.chunk
            mask = self._full_mask(C)

            def scan_fn(state, ts, xs):
                tsc = ts[: n_full * C].reshape(n_full, C)
                xsc = jax.tree.map(
                    lambda a: a[: n_full * C].reshape(
                        (n_full, C) + a.shape[1:]
                    ),
                    xs,
                )

                def body(st, inp):
                    t, x = inp
                    return self._process_impl(
                        st, t, x, mask, jnp.asarray(False), True, path
                    )

                return jax.lax.scan(body, state, (tsc, xsc))

            fn = self._scan_jitted[key] = jax.jit(scan_fn)
        return fn

    # -- impl ---------------------------------------------------------------

    def _process_impl(self, state, ts, xs, mask, final, with_outputs,
                      path: Optional[str] = None):
        m = self.monoid
        ident = m.identity()
        W, K = self.capacity, self.buffer
        tmin, tmax = self._tmin, self._tmax

        ts = jnp.asarray(ts, self.ts_dtype)
        C = ts.shape[0]
        valid = jnp.asarray(mask, bool)
        lifted = jax.vmap(jax.vmap(m.lift))(xs)  # (C, B, ...) Agg

        # -- watermark advance (monotone; final drains everything) ---------
        chunk_max = jnp.max(jnp.where(valid, ts, tmin))
        prev_max = state["max_ts"]  # the append frontier the chunk must clear
        max_ts = jnp.maximum(prev_max, chunk_max)
        wm_prev = state["wm"]
        base_wm = jnp.where(max_ts > tmin, max_ts - self.slack, tmin)
        wm = jnp.maximum(jnp.where(final, tmax, base_wm), wm_prev)
        evict_thr = jnp.where(wm > tmin, wm - self.horizon, tmin)

        # -- late-data policy ----------------------------------------------
        late = valid & (wm_prev > tmin) & (ts < wm_prev)
        if self.late_policy == "merge":
            drop = late & (ts <= evict_thr)  # unrepresentable: past the window
        else:
            drop = late
        n_late = state["n_late"] + late.sum(dtype=jnp.int32)
        n_dropped = state["n_dropped"] + drop.sum(dtype=jnp.int32)
        keep_in = valid & ~drop
        ts_in = jnp.where(keep_in, ts, tmax)
        chunk_agg = _mask_tree(lifted, keep_in, ident)

        # -- disorder-adaptive release path (core/ooo_index.py) -------------
        # A lax.cond picks, per chunk, how the sorted pending permutation is
        # produced: the d = 0 fast branch — the chunk appends at the
        # frontier (prev_max), so the permutation is pure index arithmetic
        # (compact_perm): no sort, no timestamp comparisons — vs the
        # general branch's stable argsort of the trailing (buffer ++ chunk)
        # region (never the window), which also measures the chunk's true
        # out-of-order distance from the permutation.  ONLY the (P,)
        # permutation + mask + distance cross the cond: XLA:CPU
        # conditionals copy their operands/results and block fusion, so
        # keeping the branch bodies tiny is worth ~450 us/chunk over
        # putting the merge inside.  The gathers, release split, gather-only
        # rank merge, output sweep and eviction below are branch-free, so
        # outputs are bit-exact whichever branch produced the permutation.
        win_ts, win_agg = state["win_ts"], state["win_agg"]
        buf_ts, buf_agg = state["buf_ts"], state["buf_agg"]
        P = K + C
        Mtot = W + P
        pend_ts0 = jnp.concatenate([buf_ts, ts_in])

        def _fast(_):
            if self.instrument_release:
                jax.debug.callback(lambda: _count_release("fast"))
            src, in_range = ooo_index.compact_perm(buf_ts, C, tmax=tmax)
            return src, in_range, jnp.zeros((), jnp.int32)

        def _slow(_):
            if self.instrument_release:
                jax.debug.callback(lambda: _count_release("slow"))
            order = jnp.argsort(pend_ts0, stable=True).astype(jnp.int32)
            d = ooo_index.displacement(pend_ts0, order, tmax)
            return order, jnp.ones((P,), bool), d

        if path == "fast":  # statically proven in-order (see process_chunk)
            src, in_range, d_chunk = _fast(0)
        elif path == "slow":
            src, in_range, d_chunk = _slow(0)
        else:
            fast_ok = ooo_index.chunk_in_order(ts_in, prev_max)
            src, in_range, d_chunk = jax.lax.cond(fast_ok, _fast, _slow, 0)
        ooo_recent = jnp.concatenate([state["ooo_recent"][1:], d_chunk[None]])

        # apply the permutation (identical math for both branches: the slow
        # permutation has in_range all-True, so the masking is a no-op), then
        # peel the released prefix / shift the remainder into the new buffer
        pend_agg0 = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), buf_agg, chunk_agg
        )
        pend_ts = jnp.where(in_range, pend_ts0[src], tmax)
        pend_agg = _mask_tree(_take0(pend_agg0, src), in_range, ident)
        rel_ts, rel_agg, rel, buf_ts_new, buf_agg_new, ovf_inc = (
            ooo_index.release_split(
                pend_ts, pend_agg, wm, buffer=K, tmax=tmax, ident=ident
            )
        )
        n_overflow = state["n_overflow"] + ovf_inc

        # stable gather-only merge of released elements into the window
        # (rank-dual searchsorteds — no sort, no combines; in the fast case
        # every released row lands after the window live region, and the
        # ranks come out equal to a plain append)
        mts, magg, pos_rel = ooo_index.rank_merge(
            win_ts, win_agg, rel_ts, rel_agg
        )

        # -- per-released-element outputs: fold over (ts - horizon, ts] -----
        # Released queries are monotone in both start and end — the flip
        # invariant (module docstring) — so the non-invertible path is one
        # constant-combine flip sweep instead of the old doubling table.
        if with_outputs:
            ends = pos_rel
            starts = jnp.searchsorted(
                mts, rel_ts - self.horizon, side="right"
            ).astype(jnp.int32)
            # materialize the gathered merge once: without the barrier XLA
            # re-fuses the merge gathers into every scan round of the sweep
            marr = jax.lax.optimization_barrier(magg)
            if self._use_inverse:
                ys = range_fold_invertible(m, marr, starts, ends)
            else:
                ys = flip_range_fold(
                    m, marr, starts, ends,
                    instrument="eventtime" if self.instrument_combines
                    else None,
                )
        else:
            ys = None

        # -- watermark-driven bulk eviction + window re-pack ----------------
        # Kept entries are a contiguous range of the merged (sorted) array:
        # (max(evict_thr, tmin), tmax).  Right-align its newest W entries
        # into the window with one gather — no argsort re-pack.
        lo = jnp.searchsorted(
            mts, jnp.maximum(evict_thr, tmin), side="right"
        ).astype(jnp.int32)
        hi = jnp.searchsorted(mts, tmax, side="left").astype(jnp.int32)
        n_keep = hi - lo
        wsrc = hi - W + jnp.arange(W, dtype=jnp.int32)
        valid_w = wsrc >= lo
        wsrc_c = jnp.clip(wsrc, 0, Mtot - 1)
        win_ts_new = jnp.where(valid_w, mts[wsrc_c], tmin)
        win_agg_new = _mask_tree(_take0(magg, wsrc_c), valid_w, ident)
        n_overflow = n_overflow + jnp.maximum(n_keep - W, 0)

        state = {
            "win_ts": win_ts_new,
            "win_agg": win_agg_new,
            "buf_ts": buf_ts_new,
            "buf_agg": buf_agg_new,
            "wm": wm,
            "max_ts": max_ts,
            "n_late": n_late,
            "n_dropped": n_dropped,
            "n_overflow": n_overflow,
            "ooo_recent": ooo_recent,
        }
        out = {"ts": rel_ts, "ys": ys, "mask": rel, "late": late}
        return state, out

    # -- whole stream ------------------------------------------------------

    def stream(self, ts, xs, *, state: Optional[PyTree] = None,
               flush: bool = True) -> EventTimeResult:
        """Aggregate a whole timestamped (T, B) stream chunk-by-chunk.

        Outputs are compacted with ONE host transfer at the end.  With
        ``flush=True`` (default) the reorder buffer is drained, so every
        non-dropped element is released and — when disorder ≤ slack — the
        outputs equal the in-order reference of the sorted stream.  Raises
        ``RuntimeError`` if a capacity overflowed (results would be wrong).
        """
        ts = jnp.asarray(ts, self.ts_dtype)
        T = int(ts.shape[0])
        batch = jax.tree.leaves(xs)[0].shape[1]
        if state is None:
            state = self.init_state(batch)
        if T == 0:
            if flush and bool(
                (np.asarray(state["buf_ts"]) < np.asarray(self._tmax)).any()
            ):
                raise ValueError(
                    "stream() got an empty chunk but the carried-in state has "
                    "pending reorder-buffer elements; an empty chunk cannot "
                    "fix the input shapes for the drain — call "
                    "eng.flush(state, example_row) directly"
                )
            return EventTimeResult(
                ts=np.zeros((0,), self.ts_dtype),
                ys=None,
                late_rows=np.zeros((0,), np.int64),
                n_late=int(state["n_late"]),
                n_dropped=int(state["n_dropped"]),
                state=state,
            )
        # Resolve the release branch per chunk on the HOST: the whole ts
        # array is in hand, so the device's frontier/watermark recurrence
        # can be replayed exactly (same-dtype arithmetic, identical
        # comparisons) and every chunk runs the branch-free specialized
        # program — process_chunk(path=...) — instead of the runtime
        # lax.cond (which XLA:CPU charges ~400 us/chunk in lost fusion).
        # "fast" is only claimed when the chunk provably appends at the
        # frontier with nothing late, the exact device predicate.
        ts_host = np.asarray(jax.device_get(ts))
        prev_max, prev_wm = (
            np.asarray(v) for v in jax.device_get(
                (state["max_ts"], state["wm"])
            )
        )
        tmin_h = np.asarray(jax.device_get(self._tmin))
        slack_h = np.asarray(jax.device_get(self.slack))
        C = self.chunk
        paths = []
        for lo in range(0, T, C):
            r = ts_host[lo:min(lo + C, T)]
            in_order = bool(np.all(r[1:] >= r[:-1]))
            fast = in_order and bool(r[0] >= prev_max) and bool(r[0] >= prev_wm)
            paths.append("fast" if fast else "slow")
            prev_max = np.maximum(prev_max, r.max())
            base_wm = prev_max - slack_h if prev_max > tmin_h else tmin_h
            prev_wm = np.maximum(base_wm, prev_wm)

        outs = []  # per-chunk (out dict, #real chunk rows) after the scan
        stacked = None  # (n_full, ...) leading-axis outs of the scanned prefix
        n_full = T // C
        # When every full chunk agrees on the branch — in-order streams are
        # all-fast, heavily disordered ones all-slow — the whole chunk loop
        # runs as ONE jitted lax.scan: a single dispatch for T/C chunks
        # (the per-chunk python dispatch otherwise dominates the fast path).
        use_scan = n_full >= 2 and len(set(paths[:n_full])) == 1
        if use_scan:
            state, stacked = self._stream_scan(
                T, n_full, paths[0]
            )(state, ts, xs)
            start = n_full * C
        else:
            start = 0
        for lo in range(start, T, C):
            hi = min(lo + C, T)
            pts = ts[lo:hi]
            pxs = jax.tree.map(lambda a: a[lo:hi], xs)
            if hi - lo < C:  # ragged final chunk: pad + mask
                pad = C - (hi - lo)
                pts = jnp.concatenate(
                    [pts, jnp.broadcast_to(pts[-1:], (pad,))], axis=0
                )
                pxs = jax.tree.map(
                    lambda a: jnp.concatenate(
                        [a, jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])], 0
                    ),
                    pxs,
                )
                mask = jnp.arange(C) < (hi - lo)
            else:
                mask = None
            state, out = self.process_chunk(
                state, pts, pxs, mask, path=paths[lo // C]
            )
            outs.append((out, hi - lo))
        if flush and T > 0:
            state, out = self.flush(state, jax.tree.map(lambda a: a[:1], xs))
            outs.append((out, 0))

        # one host transfer for everything; the per-chunk outputs are
        # concatenated HOST-side with numpy (a device jnp.concatenate over
        # ~T/C small operands costs more in dispatch than the chunk loop)
        host = jax.device_get(
            {
                "stacked": stacked,
                "outs": [o for o, _ in outs],
                "counters": {
                    k: state[k] for k in ("n_late", "n_dropped", "n_overflow")
                },
            }
        )
        if int(host["counters"]["n_overflow"]) > 0:
            raise RuntimeError(
                f"event-time engine overflow "
                f"({int(host['counters']['n_overflow'])} elements lost): "
                f"raise capacity= (live in-horizon elements) or buffer= "
                f"(reorder slots) for this stream"
            )

        def flat2(a):  # (n_full, L, ...) scan stack -> (n_full*L, ...)
            return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])

        parts_ts, parts_mask, parts_late, parts_ys = [], [], [], []
        if host["stacked"] is not None:
            s = host["stacked"]
            parts_ts.append(flat2(s["ts"]))
            parts_mask.append(flat2(s["mask"]))
            parts_late.append(flat2(s["late"]))
            parts_ys.append(jax.tree.map(flat2, s["ys"]))
        for o, n in zip(host["outs"], (n for _, n in outs)):
            parts_ts.append(o["ts"])
            parts_mask.append(o["mask"])
            parts_late.append(o["late"][:n])
            if o["ys"] is not None:
                parts_ys.append(o["ys"])
        ts_all = np.concatenate(parts_ts)
        sel = np.concatenate(parts_mask)
        late_all = (
            np.concatenate(parts_late) if parts_late
            else np.zeros((0,), bool)
        )
        ys_all = (
            jax.tree.map(
                lambda *ps: np.concatenate(ps, axis=0), *parts_ys
            )
            if parts_ys else None
        )
        return EventTimeResult(
            ts=ts_all[sel],
            ys=jax.tree.map(lambda a: a[sel], ys_all)
            if ys_all is not None else None,
            late_rows=np.nonzero(late_all)[0],
            n_late=int(host["counters"]["n_late"]),
            n_dropped=int(host["counters"]["n_dropped"]),
            state=state,
        )


# ---------------------------------------------------------------------------
# Oracle
# ---------------------------------------------------------------------------


def in_order_reference(monoid: Monoid, ts, xs, horizon):
    """Per-element oracle: stable-sort by timestamp, then for each element
    fold (left-to-right, older operand LEFT) everything with
    ``ts' > ts - horizon``.

    Returns ``(sorted_ts, (T, B, ...) aggregates)`` — what a per-element
    :class:`TimestampedWindow` scan of the in-order stream emits, and what
    :meth:`EventTimeChunkedStream.stream` must reproduce whenever disorder
    ≤ slack.  Sorted row i's window is the run ``[lo_i, i]`` of the sorted
    stream; every row runs its own fold ``acc = acc ⊗ x[lo_i + k]`` from
    the identity, all rows side by side, one window offset ``k`` per step
    (a row whose window is shorter keeps its acc).  O(T · window) combines
    in O(window) dispatches — a test oracle, not an engine.
    """
    ts = np.asarray(ts)
    order = np.argsort(ts, kind="stable")
    sts = ts[order]
    T = int(sts.shape[0])
    lo = np.searchsorted(sts, sts - horizon, side="right")
    rows = np.arange(T)
    lifted = jax.vmap(jax.vmap(monoid.lift))(xs)
    lifted = jax.tree.map(lambda a: a[jnp.asarray(order)], lifted)
    ident = monoid.identity()
    acc = jax.tree.map(
        lambda a, i: jnp.broadcast_to(jnp.asarray(i, a.dtype), a.shape),
        lifted, ident,
    )

    @jax.jit
    def step(acc, x, take):
        nxt = jax.vmap(monoid.combine)(acc, x)
        return _where_rows(take, nxt, acc)

    for k in range(int(np.max(rows - lo + 1, initial=0))):
        src = lo + k
        x = _take0(lifted, jnp.asarray(np.minimum(src, T - 1)))
        acc = step(acc, x, jnp.asarray(src <= rows))
    return sts, acc
