"""Keyed window store — per-key sliding windows at million-key scale.

The batched/chunked engines maintain B windows in lock-step: every lane sees
every element.  A multi-tenant system is the transpose — each event belongs
to ONE key (user, request, partition) out of an unbounded universe, and only
that key's window moves.  This module provides that layer over the existing
SWAG machinery:

  * :class:`KeyDirectory` — a JAX-native open-addressing hash directory
    (key → dense slot): vectorized lookup, sequential-per-new-key admission
    fused into the chunk dispatch, LRU eviction when the slot pool is full
    and TTL expiry for idle keys — the hot set stays bounded (``slots``)
    while the key universe is unbounded.
  * :class:`KeyedWindowStore` — ``slots`` independent count-based windows
    stored as stacked SoA lanes of the warm-carry representation
    (:mod:`repro.core.swag_base`): lane t of a slot's carry is the suffix
    fold of its last ``window - 1 - t`` elements.  Any bulk-protocol SWAG
    algorithm interoperates: ``export_states`` / ``adopt_states`` convert
    lanes to/from live per-element states via ``carry_to_state`` /
    ``state_to_carry``.
  * :meth:`KeyedWindowStore.update_chunk` — the bulk path: a mixed-key
    ``(key, x)`` chunk becomes ONE fused segment-wise dispatch: stable sort
    by key (arrival order preserved within key — non-commutative monoids
    stay bit-exact vs the per-key per-element reference), segment
    boundaries, directory admission, per-row window outputs via a
    constant-combine segmented two-stacks flip sweep (the flip invariant —
    see the :mod:`repro.core.event_time` module docstring, the ONE place
    stating it and the suffix-scan operand-order rule), and one scatter of
    refreshed carries — instead of K tiny per-key updates (cf. the
    bulk-eviction direction of arXiv 2307.11210, extended across the key
    dimension).

The hot-path anatomy keeps every per-dispatch cost proportional to the
CHUNK, never to the slot pool:

  1. stable sort by key → segments (O(C log C));
  2. admission (:meth:`KeyDirectory.admit_heads`): ONE vectorized lookup of
     the segment-head keys; a ``lax.cond`` takes the all-hit branch (just a
     recency-bump scatter) when the chunk introduces no new keys, else a
     round-based *batched* admission that inserts every genuinely-new head
     per round with scatter-min conflict resolution — sequential only in
     the (few) probe-conflict rounds, not per key;
  3. per-row outputs from the intra-chunk flip sweep — one segmented
     prefix scan + one segmented suffix scan at W-aligned block boundaries
     (O(1) ⊗ per row, flat in W; invertible commutative monoids keep the
     one-prefix-scan ``range_fold_invertible`` fast path) — plus a
     warm-prefix gather of (C, h) carry lanes; reclaimed slots are masked
     at the GATHER (never a full-(slots, h) reset pass);
  4. refreshed carries from one more segmented suffix scan
     (:func:`seg_suffix_scan` / :func:`seg_prefix_scan`, or the fused
     ``kernels/seg_scan`` Pallas kernels for scalar monoids on TPU) fused
     into two masked gathers and ONE batched (C, h) scatter.

:class:`KeyedChunkedStream` donates the state buffers into the jitted
update, so that scatter is in-place — per-chunk work stays O(C·h) while
the resident state is O(slots·h).
  * :class:`KeyedChunkedStream` — the chunk-at-a-time driver (jit cache,
    ragged final chunk padding) mirroring
    :class:`repro.core.chunked.ChunkedStream`.
  * :class:`ShardedKeyedStore` — device sharding of the key space:
    ``shard_map`` over a mesh axis, key → shard by hash, per-shard stores
    and directories, ZERO collectives in steady state (each shard masks the
    chunk down to its own rows; outputs stay shard-local).

Keys are non-negative int32 (hash-partition larger key spaces before
ingest); ``-1``/``-2`` are directory sentinels.  Within one chunk at most
``slots`` distinct keys can be admitted (later ones are counted in
``n_dropped`` and emit identity outputs) and an LRU victim is never a slot
already touched by the same chunk, so slot assignment is deterministic.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ooo_index, swag_base
from repro.core.event_time import (
    COMBINE_COUNTS,
    counting_combines,
    flip_range_fold,
    range_fold_invertible,
    reset_combine_counts,
    seg_prefix_scan,
    seg_suffix_scan,
)
from repro.core.monoids import Monoid, _hash_u32
from repro.core.swag_base import chunk_length
from repro.obs import counters as obs_counters

__all__ = [
    "KeyDirectory",
    "KeyedWindowStore",
    "KeyedChunkedStream",
    "ShardedKeyedStore",
    "COMBINE_COUNTS",
    "counting_combines",
    "reset_combine_counts",
    "seg_prefix_scan",
    "seg_suffix_scan",
]

PyTree = Any

EMPTY = jnp.int32(-1)  # free table entry / free slot
DELETED = jnp.int32(-2)  # tombstone: probes continue through it
_KEY_SENTINEL = jnp.int32(2**31 - 1)  # masked rows sort last


# Host-side admission-branch counters (filled only by stores built with
# ``instrument_admission=True`` — a jax.debug.callback in each branch of the
# admission cond, so tests can assert the hit branch was actually taken at
# runtime).  The counters live in :mod:`repro.obs.counters` (one home for
# the effects-barrier-before-read rule); ``ADMISSION_COUNTS`` is a thin
# deprecated alias — barriered reads should go through
# ``obs_counters.admission.read()``.
ADMISSION_COUNTS = obs_counters.admission


def reset_admission_counts() -> None:
    obs_counters.admission.reset()


def _count_admission(branch: str) -> None:
    obs_counters.admission.bump(branch)


def _bc(mask, leaf):
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - mask.ndim))


def _where_rows(mask, a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: jnp.where(_bc(mask, x), x, y), a, b)


def _mask_tree(tree: PyTree, mask, ident: PyTree) -> PyTree:
    return jax.tree.map(
        lambda a, i: jnp.where(_bc(mask, a), a, jnp.asarray(i, a.dtype)),
        tree,
        ident,
    )


def _take0(tree: PyTree, idx) -> PyTree:
    return jax.tree.map(lambda a: a[idx], tree)


def _shift_lanes(tree: PyTree, shift, h: int) -> PyTree:
    """Lane t of row k becomes lane ``min(t + shift_k, h - 1)`` of the same
    row, for ``(C, h, ...)`` leaves: one static left shift by 2**i (last
    lane repeated) per bit of ``min(shift, h - 1)``, taken by an
    elementwise select where the row's bit is set.  Clamped shifts compose
    (``min(min(t + a, h-1) + b, h-1) == min(t + a + b, h-1)``), so the
    result equals the clipped-index lane gather bit for bit."""
    s = jnp.clip(shift, 0, h - 1)
    for i in range((h - 1).bit_length()):
        d = 1 << i
        take = (s & d) != 0

        def step(x):
            tail = jnp.broadcast_to(x[:, -1:], (x.shape[0], d) + x.shape[2:])
            moved = jnp.concatenate([x[:, d:], tail], axis=1)
            return jnp.where(_bc(take, x), moved, x)

        tree = jax.tree.map(step, tree)
    return tree


def _row_windows(tree: PyTree, end, width: int) -> PyTree:
    """Row k becomes rows ``end_k - width + 1 .. end_k`` of the ``(C, ...)``
    leaves along a new axis 1, one contiguous run per row.  The ``(C,
    width)`` Hankel view (row i = rows ``i - width + 1 .. i``) is built
    from static row slices, doubling its width per pass, and then
    row-gathered by ``end``.  Zero rows stand in for the positions before
    row 0 (callers must mask those lanes).  (A ``dynamic_slice`` per row
    compiles for a TPU v5e to a sequential loop over the rows.)"""

    def take(x):
        C = x.shape[0]
        zeros = lambda n: jnp.zeros((n,) + x.shape[1:], x.dtype)
        # right padding: the passes below drop up to 2 * width - 3 rows
        hk = jnp.concatenate([zeros(width - 1), x, zeros(width)])[:, None]
        k = 1  # hk[i, j] == padded row i + j, for j < k
        while k < width:
            n = hk.shape[0] - k
            hk = jnp.concatenate(
                [hk[:n], hk[k : k + n, : min(k, width - k)]], axis=1
            )
            k *= 2
        return hk[:C][end]

    return jax.tree.map(take, tree)


# The segmented scans (seg_suffix_scan / seg_prefix_scan) live in
# :mod:`repro.core.event_time` next to the flip-invariant statement they
# implement; they are re-exported above for back-compat.


# ---------------------------------------------------------------------------
# Key directory
# ---------------------------------------------------------------------------


class KeyDirectory:
    """Open-addressing key → slot directory as plain JAX arrays.

    ``slots`` dense window slots are addressed through a power-of-two probe
    table of ``dir_factor * slots`` entries (linear probing, ≤ ``probes``
    steps, tombstoned deletes that inserts reuse).  All operations are pure
    functions of the state dict, usable inside jit:

      * :meth:`lookup` — fully vectorized (C, probes) gather for a whole
        chunk of keys;
      * :meth:`admit_row` — one key: find-or-allocate.  Allocation takes a
        free slot while any exists, else evicts the least-recently-used
        slot NOT touched by the current chunk (``touched``) and tombstones
        its table entry.  Taken-branch ``lax.cond`` keeps the hit path at
        O(probes);
      * :meth:`expire` — vectorized TTL sweep freeing every slot idle
        longer than ``ttl``.
    """

    def __init__(self, slots: int, *, dir_factor: int = 2, probes: int = 32):
        self.slots = int(slots)
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        size = 8
        while size < dir_factor * self.slots:
            size *= 2
        self.size = size
        self.probes = min(int(probes), size)

    def init(self) -> PyTree:
        return {
            "table_key": jnp.full((self.size,), EMPTY, jnp.int32),
            "table_slot": jnp.zeros((self.size,), jnp.int32),
            "slot_key": jnp.full((self.slots,), EMPTY, jnp.int32),
            "last_used": jnp.full((self.slots,), -jnp.inf, jnp.float32),
            "n_live": jnp.zeros((), jnp.int32),
            "n_evicted": jnp.zeros((), jnp.int32),
            "n_failed": jnp.zeros((), jnp.int32),
        }

    def _probe_pos(self, key):
        h = _hash_u32(jnp.asarray(key, jnp.int32), 0).astype(jnp.int32)
        offs = jnp.arange(self.probes, dtype=jnp.int32)
        return (h + offs) & jnp.int32(self.size - 1)

    def lookup(self, state: PyTree, keys) -> tuple:
        """Vectorized chunk lookup: ``(slots, found)`` — slot -1 if absent.
        Negative keys (the sentinel range) are never found, so callers may
        pad query batches with -1."""
        keys = jnp.asarray(keys, jnp.int32)
        pos = jax.vmap(self._probe_pos)(keys)  # (C, P)
        tk = state["table_key"][pos]
        eq = tk == keys[:, None]
        empty = tk == EMPTY
        before = jnp.cumsum(empty.astype(jnp.int32), axis=1) - empty
        hit = eq & (before == 0)
        found = hit.any(axis=1) & (keys >= 0)
        j = jnp.argmax(hit, axis=1)
        slot = state["table_slot"][
            jnp.take_along_axis(pos, j[:, None], axis=1)[:, 0]
        ]
        return jnp.where(found, slot, -1), found

    def admit_row(self, state: PyTree, touched, key, ts):
        """Find-or-allocate one key; returns ``(state, touched, slot, new)``.

        ``touched`` is the (slots,) mask of slots used by the current chunk
        — LRU eviction never reclaims one, so a chunk with more distinct
        keys than free+evictable slots fails the excess admissions
        (slot -1, ``n_failed``) instead of corrupting earlier segments.
        """
        key = jnp.asarray(key, jnp.int32)
        ts = jnp.asarray(ts, jnp.float32)
        pos = self._probe_pos(key)
        tk = state["table_key"][pos]
        eq = tk == key
        empty = tk == EMPTY
        free = empty | (tk == DELETED)
        before = jnp.cumsum(empty.astype(jnp.int32)) - empty
        hit = eq & (before == 0)
        found = hit.any()

        def on_found(st, tch):
            slot = st["table_slot"][pos[jnp.argmax(hit)]]
            st = dict(st, last_used=st["last_used"].at[slot].set(ts))
            return st, tch.at[slot].set(True), slot, jnp.asarray(False)

        def on_miss(st, tch):
            ins_ok = free.any()
            ins_pos = pos[jnp.argmax(free)]
            use_free = st["n_live"] < self.slots
            free_slot = jnp.argmax(st["slot_key"] == EMPTY).astype(jnp.int32)
            cost = jnp.where(tch, jnp.inf, st["last_used"])
            victim = jnp.argmin(cost).astype(jnp.int32)
            evict_ok = jnp.isfinite(cost[victim])
            slot = jnp.where(use_free, free_slot, victim)
            ok = ins_ok & (use_free | evict_ok)
            evicting = ok & ~use_free
            # tombstone the victim's table entry (guarded drop-scatter)
            old_key = st["slot_key"][victim]
            vpos = self._probe_pos(old_key)
            vtk = st["table_key"][vpos]
            vempty = vtk == EMPTY
            vbefore = jnp.cumsum(vempty.astype(jnp.int32)) - vempty
            vhit = (vtk == old_key) & (vbefore == 0)
            vslot = jnp.where(
                evicting & vhit.any(), vpos[jnp.argmax(vhit)], self.size
            )
            table_key = st["table_key"].at[vslot].set(DELETED, mode="drop")
            wr = jnp.where(ok, ins_pos, self.size)
            sl = jnp.where(ok, slot, self.slots)
            st = dict(
                st,
                table_key=table_key.at[wr].set(key, mode="drop"),
                table_slot=st["table_slot"].at[wr].set(slot, mode="drop"),
                slot_key=st["slot_key"].at[sl].set(key, mode="drop"),
                last_used=st["last_used"].at[sl].set(ts, mode="drop"),
                n_live=st["n_live"] + (ok & use_free),
                n_evicted=st["n_evicted"] + evicting,
                n_failed=st["n_failed"] + ~ok,
            )
            tch = tch.at[sl].set(True, mode="drop")
            return st, tch, jnp.where(ok, slot, -1), ok

        return jax.lax.cond(found, on_found, on_miss, state, touched)

    def admit_heads(self, state: PyTree, keys, tss, head_mask, *,
                    instrument: bool = False):
        """Chunk-wide find-or-allocate for the segment-head keys (the bulk
        counterpart of :meth:`admit_row`); returns ``(state, slots, new)``
        with (C,) per-row slots (-1 off-head / failed) and new-key flags.

        ONE vectorized lookup resolves every already-admitted head; a
        ``lax.cond`` then skips allocation entirely when the chunk has no
        new keys (the steady-state fast path is a single recency-bump
        scatter).  Otherwise the genuinely-new heads are admitted in
        *batched rounds*: each round probes all pending keys at once,
        resolves probe-cell conflicts by scatter-min (lowest head index
        wins a cell), assigns winners consecutive slots from a candidate
        list precomputed ONCE (free slots in index order, then evictable
        slots in LRU order — never a slot held by a key of this chunk), and
        tombstones + inserts them in bulk.  Every round admits at least one
        pending head, so the while_loop runs O(probe-conflict chain) rounds
        of O(C · probes) vector work — not one sequential step per key.

        Heads whose probe window is full, or that arrive after the
        free+evictable budget is spent, fail safely (slot -1,
        ``n_failed``); which head pays for capacity exhaustion can differ
        from :meth:`admit_row`'s strict one-at-a-time order under probe
        conflicts, but the outcome is deterministic.
        """
        S, size, P = self.slots, self.size, self.probes
        keys = jnp.asarray(keys, jnp.int32)
        tss = jnp.asarray(tss, jnp.float32)
        head_mask = jnp.asarray(head_mask, bool)
        C = int(keys.shape[0])
        idx_c = jnp.arange(C, dtype=jnp.int32)

        slot0, found = self.lookup(state, jnp.where(head_mask, keys, EMPTY))
        found_scat = jnp.where(found, slot0, S)
        # recency bump for every already-admitted head (one scatter)
        state = dict(
            state,
            last_used=state["last_used"].at[found_scat].set(tss, mode="drop"),
        )
        pending0 = head_mask & ~found

        def hits_only(st):
            if instrument:
                jax.debug.callback(_count_admission, "fast")
            return st, slot0, jnp.zeros((C,), bool)

        def with_admission(st):
            if instrument:
                jax.debug.callback(_count_admission, "slow")
            touched = jnp.zeros((S,), bool).at[found_scat].set(
                True, mode="drop"
            )
            live = st["slot_key"] != EMPTY
            free_slots = ~live
            evictable = live & ~touched & jnp.isfinite(st["last_used"])
            klass = jnp.where(
                free_slots, 0, jnp.where(evictable, 1, 2)
            ).astype(jnp.int32)
            order_key = jnp.where(
                free_slots,
                jnp.arange(S, dtype=jnp.float32),
                st["last_used"],
            )
            cand = jnp.lexsort((order_key, klass)).astype(jnp.int32)
            n_avail = (klass < 2).sum(dtype=jnp.int32)
            n_free0 = free_slots.sum(dtype=jnp.int32)
            pos_all = jax.vmap(self._probe_pos)(keys)  # (C, P)

            def round_body(carry):
                st, pending, slots, new, consumed = carry
                tk = st["table_key"][pos_all]
                empty = tk == EMPTY
                free = empty | (tk == DELETED)
                has_cell = free.any(axis=1)
                ins_j = jnp.argmax(free, axis=1)
                ins_pos = jnp.take_along_axis(
                    pos_all, ins_j[:, None], axis=1
                )[:, 0]
                ins_ok = pending & has_cell
                fail_now = pending & ~has_cell
                # conflict resolution: lowest head index wins each cell
                claims = jnp.full((size,), C, jnp.int32).at[
                    jnp.where(ins_ok, ins_pos, size)
                ].min(idx_c, mode="drop")
                win = ins_ok & (claims[ins_pos] == idx_c)
                rank = jnp.cumsum(win.astype(jnp.int32)) - 1
                cand_idx = consumed + jnp.where(win, rank, 0)
                alloc_ok = win & (cand_idx < n_avail)
                cap_fail = win & ~(cand_idx < n_avail)
                slot = cand[jnp.clip(cand_idx, 0, S - 1)]
                evicting = alloc_ok & (cand_idx >= n_free0)
                # tombstone the evicted tenants' table entries (each live
                # key holds exactly one entry, so victim writes never clash)
                old_key = st["slot_key"][slot]
                vpos = jax.vmap(self._probe_pos)(old_key)
                vtk = st["table_key"][vpos]
                vempty = vtk == EMPTY
                vbefore = jnp.cumsum(vempty.astype(jnp.int32), axis=1) - vempty
                vhit = (vtk == old_key[:, None]) & (vbefore == 0)
                vdst = jnp.where(
                    evicting & vhit.any(axis=1),
                    jnp.take_along_axis(
                        vpos, jnp.argmax(vhit, axis=1)[:, None], axis=1
                    )[:, 0],
                    size,
                )
                table_key = st["table_key"].at[vdst].set(DELETED, mode="drop")
                wdst = jnp.where(alloc_ok, ins_pos, size)
                sdst = jnp.where(alloc_ok, slot, S)
                st = dict(
                    st,
                    table_key=table_key.at[wdst].set(keys, mode="drop"),
                    table_slot=st["table_slot"].at[wdst].set(slot, mode="drop"),
                    slot_key=st["slot_key"].at[sdst].set(keys, mode="drop"),
                    last_used=st["last_used"].at[sdst].set(tss, mode="drop"),
                    n_live=st["n_live"]
                    + (alloc_ok & ~evicting).sum(dtype=jnp.int32),
                    n_evicted=st["n_evicted"] + evicting.sum(dtype=jnp.int32),
                    n_failed=st["n_failed"]
                    + (fail_now | cap_fail).sum(dtype=jnp.int32),
                )
                return (
                    st,
                    pending & ~(alloc_ok | fail_now | cap_fail),
                    jnp.where(alloc_ok, slot, slots),
                    new | alloc_ok,
                    consumed + alloc_ok.sum(dtype=jnp.int32),
                )

            st, _, slots, new, _ = jax.lax.while_loop(
                lambda c: c[1].any(),
                round_body,
                (st, pending0, slot0, jnp.zeros((C,), bool), jnp.int32(0)),
            )
            return st, slots, new

        return jax.lax.cond(pending0.any(), with_admission, hits_only, state)

    def expire(self, state: PyTree, now, ttl) -> tuple:
        """Free every slot idle longer than ``ttl``; returns
        ``(state, expired)`` with the (slots,) expiry mask (vectorized)."""
        now = jnp.asarray(now, jnp.float32)
        live = state["slot_key"] != EMPTY
        expired = live & (now - state["last_used"] > jnp.asarray(ttl, jnp.float32))
        te_slot = jnp.clip(state["table_slot"], 0, self.slots - 1)
        kill = (state["table_key"] >= 0) & expired[te_slot]
        state = dict(
            state,
            table_key=jnp.where(kill, DELETED, state["table_key"]),
            slot_key=jnp.where(expired, EMPTY, state["slot_key"]),
            last_used=jnp.where(expired, -jnp.inf, state["last_used"]),
            n_live=state["n_live"] - expired.sum(dtype=jnp.int32),
            n_evicted=state["n_evicted"] + expired.sum(dtype=jnp.int32),
        )
        return state, expired


# ---------------------------------------------------------------------------
# The keyed store
# ---------------------------------------------------------------------------


class KeyedWindowStore:
    """``slots`` independent per-key count windows as stacked carry lanes.

    State layout (SoA, one leading slot axis everywhere):

      * ``carry``  (slots, window-1, ...) — per-slot warm-carry tails
        (entry t = suffix fold of the slot's last ``window-1-t`` elements,
        front-truncated; the exact representation of
        :mod:`repro.core.swag_base`'s carry protocol);
      * ``last``   (slots, ...)           — the slot's latest window
        aggregate (what :meth:`query` serves);
      * ``n_seen`` (slots,)               — elements ever folded per slot;
      * ``dir``                           — the :class:`KeyDirectory` state;
      * ``tick``   ()                     — default recency clock;
      * ``carry_ts`` (slots, window-1)    — HORIZON MODE ONLY: lane t holds
        the timestamp of the slot's ``window-1-t``-th-from-last element
        (``-inf`` where that element does not exist yet).

    ``horizon=`` switches the store from count windows to true EVENT-TIME
    windows: row j's output folds its key's elements with timestamp
    ``> ts_j - horizon`` (still capped at the last ``window`` elements —
    ``window`` becomes the static per-key capacity).  Expiry is watermark-
    driven and READ-side: the warm-prefix gather selects carry lane
    ``max(p, t*)`` where ``t* = #{lane_ts <= ts_j - horizon}`` counts the
    expired history lanes, and the in-chunk span start comes from the
    per-segment finger search :func:`repro.core.ooo_index
    .seg_bounded_search` — no per-slot sweep ever runs, the one-gather/
    one-scatter carry refresh (and its donation) is preserved, with
    ``carry_ts`` refreshed by the same shifted-lane/from-chunk ladder as
    ``carry``.  Precondition: each key's timestamps must be non-decreasing
    in arrival order (chain the store behind :class:`repro.core.event_time
    .EventTimeChunkedStream`, whose released rows are globally sorted).
    ``horizon=None`` keeps the count path byte-identical.

    :meth:`update_chunk` is pure (jit it, or use :class:`KeyedChunkedStream`
    which caches the jit per chunk length).
    """

    def __init__(
        self,
        monoid: Monoid,
        window: int,
        slots: int,
        *,
        dir_factor: int = 2,
        probes: int = 32,
        ttl: Optional[float] = None,
        horizon: Optional[float] = None,
        use_inverse: Optional[bool] = None,
        use_seg_kernel: Optional[bool] = None,
        instrument_admission: bool = False,
        instrument_combines: bool = False,
        obs: Optional[Any] = None,
    ):
        # obs: a repro.obs.registry.ObsConfig — the one observability gate.
        # Disabled (or None) contributes NOTHING to the traced computation
        # (tests assert jaxpr equality); enabled folds its instrument flags
        # into the jit-visible hooks below.
        if obs is not None and obs.enabled:
            instrument_admission = instrument_admission or obs.instrument_admission
            instrument_combines = instrument_combines or obs.instrument_combines
        self.obs = obs if (obs is not None and obs.enabled) else None
        self.monoid = monoid
        self.window = int(window)
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.h = self.window - 1
        self.slots = int(slots)
        self.directory = KeyDirectory(slots, dir_factor=dir_factor, probes=probes)
        self.ttl = ttl
        if horizon is not None and float(horizon) <= 0:
            raise ValueError(f"horizon must be > 0, got {horizon}")
        self.horizon = None if horizon is None else float(horizon)
        if use_inverse is None:
            use_inverse = monoid.invertible and monoid.commutative
        self.use_inverse = bool(use_inverse)
        # seg_scan Pallas kernels: None = auto (scalar-monoid gate AND TPU
        # backend), True = force (raises for unsupported monoids), False =
        # always the lax associative_scan path.
        self.use_seg_kernel = use_seg_kernel
        self.instrument_admission = bool(instrument_admission)
        # instrument_combines routes every sweep ⊗ through
        # ``COMBINE_COUNTS["keyed"]`` — forces the lax scan path (the Pallas
        # kernel cannot host the debug callback).
        self.instrument_combines = bool(instrument_combines)

    def _kernel_op(self) -> Optional[str]:
        """The seg_scan kernel op for this store, or None for the lax path."""
        use = self.use_seg_kernel
        if self.instrument_combines or not (use is None or use):
            return None
        from repro.kernels.ops_registry import default_interpret, op_for_monoid

        op = op_for_monoid(self.monoid)
        if use is None:
            return op if (op is not None and not default_interpret()) else None
        if op is None:
            raise ValueError(
                "use_seg_kernel=True needs a scalar-op monoid "
                f"(got {getattr(self.monoid, 'name', self.monoid)!r})"
            )
        return op

    def _sweep_monoid(self) -> Monoid:
        return (counting_combines(self.monoid, "keyed")
                if self.instrument_combines else self.monoid)

    def _seg_scan(self, end_flags, lifted: PyTree) -> PyTree:
        """Segmented suffix scan over the sorted chunk — the fused
        ``kernels/seg_scan`` Pallas kernel when the monoid passes the
        scalar-monoid structural gate (auto: only on TPU; ``interpret``
        under the kernel keeps CPU tests exact), else the generic
        :func:`seg_suffix_scan` lax fallback."""
        op = self._kernel_op()
        if op is not None:
            from repro.kernels.seg_scan.ops import seg_suffix_scan_op

            leaves, treedef = jax.tree.flatten(lifted)
            out = seg_suffix_scan_op(leaves[0], end_flags, op)
            return jax.tree.unflatten(treedef, [out])
        return seg_suffix_scan(self._sweep_monoid(), end_flags, lifted)

    def _seg_pscan(self, start_flags, lifted: PyTree) -> PyTree:
        """Segmented PREFIX scan — the mirror of :meth:`_seg_scan`, behind
        the same kernel gate (``kernels/seg_scan``'s prefix variant on TPU,
        :func:`seg_prefix_scan` lax fallback)."""
        op = self._kernel_op()
        if op is not None:
            from repro.kernels.seg_scan.ops import seg_prefix_scan_op

            leaves, treedef = jax.tree.flatten(lifted)
            out = seg_prefix_scan_op(leaves[0], start_flags, op)
            return jax.tree.unflatten(treedef, [out])
        return seg_prefix_scan(self._sweep_monoid(), start_flags, lifted)

    # -- state -------------------------------------------------------------

    def init_state(self) -> PyTree:
        ident = self.monoid.identity()

        def fill(lead: tuple):
            return jax.tree.map(
                lambda i: jnp.broadcast_to(
                    jnp.asarray(i), lead + jnp.asarray(i).shape
                ).copy(),
                ident,
            )

        state = {
            "dir": self.directory.init(),
            "carry": fill((self.slots, self.h)),
            "last": fill((self.slots,)),
            "n_seen": jnp.zeros((self.slots,), jnp.int32),
            "tick": jnp.zeros((), jnp.float32),
            "n_dropped": jnp.zeros((), jnp.int32),
        }
        if self.horizon is not None:
            # -inf = "no such element yet": those lanes always count as
            # expired in the read-side lane selection, so a cold key's
            # front-truncated lanes are skipped without any extra mask
            state["carry_ts"] = jnp.full(
                (self.slots, self.h), -jnp.inf, jnp.float32
            )
        return state

    def query(self, state: PyTree, keys) -> tuple:
        """Latest window aggregate per key: ``(aggs, found)`` — identity for
        unknown keys.  Pure; vectorized over (C,) keys."""
        keys = jnp.asarray(keys, jnp.int32)
        slot, found = self.directory.lookup(state["dir"], keys)
        aggs = _take0(state["last"], jnp.clip(slot, 0, self.slots - 1))
        return _mask_tree(aggs, found, self.monoid.identity()), found

    def counters(self, state: PyTree) -> dict:
        """Store health counters as DEVICE scalars (no host sync — the obs
        registry batches the transfer at scrape; callers reading directly
        should ``jax.device_get`` the dict)."""
        d = state["dir"]
        return {
            "n_live": d["n_live"],
            "n_evicted": d["n_evicted"],
            "n_failed": d["n_failed"],
            "n_dropped": state["n_dropped"],
        }

    def expire(self, state: PyTree, now=None) -> PyTree:
        """TTL sweep: evict every key idle longer than ``ttl`` and reset its
        lanes (pure; no-op when ``ttl`` is None)."""
        if self.ttl is None:
            return state
        now = state["tick"] if now is None else jnp.asarray(now, jnp.float32)
        dir_state, expired = self.directory.expire(state["dir"], now, self.ttl)
        state = dict(
            state,
            dir=dir_state,
            carry=self._reset_lanes(state["carry"], expired),
            last=self._reset_lanes(state["last"], expired),
            n_seen=jnp.where(expired, 0, state["n_seen"]),
        )
        if self.horizon is not None:
            state["carry_ts"] = jnp.where(
                expired[:, None], -jnp.inf, state["carry_ts"]
            )
        return state

    def _reset_lanes(self, lanes: PyTree, mask) -> PyTree:
        ident = self.monoid.identity()
        return jax.tree.map(
            lambda a, i: jnp.where(
                mask.reshape((self.slots,) + (1,) * (a.ndim - 1)),
                jnp.asarray(i, a.dtype),
                a,
            ),
            lanes,
            ident,
        )

    # -- the fused chunk update --------------------------------------------

    def update_chunk(self, state: PyTree, keys, xs, ts=None, mask=None):
        """One mixed-key chunk: ``keys`` (C,), ``xs`` (C, ...) raw inputs.

        Returns ``(state, ys, info)``: ``ys`` (C, ...) per-row window
        aggregates (pre-``lower``) aligned with the inputs — row j is the
        fold of the last ``min(window, seen)`` elements OF ROW j'S KEY —
        and ``info`` with per-row ``slots`` / ``dropped`` and the admission
        counters.  ``ts`` (scalar or (C,)) feeds directory recency (and the
        TTL clock); defaults to an internal tick.  ``mask`` (C,) pads a
        ragged final chunk (False rows are ignored and emit identities).

        In ``horizon=`` mode row j instead folds its key's elements with
        timestamp ``> ts_j - horizon`` (capped at the last ``window``),
        where ``ts`` doubles as the event time.  PRECONDITION: each key's
        timestamps must be non-decreasing in arrival order (feed released
        rows of an :class:`repro.core.event_time.EventTimeChunkedStream`);
        violating it silently returns wrong folds, exactly like violating
        the flip invariant.
        """
        m = self.monoid
        ident = m.identity()
        S, W, h = self.slots, self.window, self.h
        keys = jnp.asarray(keys, jnp.int32)
        C = int(keys.shape[0])
        valid = jnp.ones((C,), bool) if mask is None else jnp.asarray(mask, bool)
        tick = state["tick"] + 1.0
        if ts is None:
            ts_row = jnp.broadcast_to(tick, (C,))
        else:
            ts_row = jnp.broadcast_to(jnp.asarray(ts, jnp.float32), (C,))

        # -- stable sort by key: segments, arrival order kept within key --
        order = jnp.argsort(jnp.where(valid, keys, _KEY_SENTINEL), stable=True)
        inv = jnp.argsort(order)
        ks = keys[order]
        vs = valid[order]
        tss = ts_row[order]
        xss = _take0(xs, order)
        idx = jnp.arange(C, dtype=jnp.int32)
        prev = jnp.concatenate([ks[:1] - 1, ks[:-1]])
        seg_head = vs & ((idx == 0) | (ks != prev))
        nxt_head = jnp.concatenate([seg_head[1:], jnp.ones((1,), bool)])
        nxt_invalid = jnp.concatenate([~vs[1:], jnp.ones((1,), bool)])
        seg_end = vs & (nxt_head | nxt_invalid)
        sid = jnp.clip(jnp.cumsum(seg_head.astype(jnp.int32)) - 1, 0, C - 1)

        # -- directory admission: one vectorized pass over segment HEADS --
        dir_state, head_slots, new_heads = self.directory.admit_heads(
            state["dir"],
            ks,
            tss,
            seg_head,
            instrument=self.instrument_admission,
        )

        # -- per-segment fields broadcast to rows --------------------------
        scat = jnp.where(seg_head, sid, C)
        head_pos = jnp.zeros((C,), jnp.int32).at[scat].set(idx, mode="drop")
        slot_by_seg = jnp.full((C,), -1, jnp.int32).at[scat].set(
            head_slots, mode="drop"
        )
        new_by_seg = jnp.zeros((C,), bool).at[scat].set(new_heads, mode="drop")
        end_pos = jnp.zeros((C,), jnp.int32).at[
            jnp.where(seg_end, sid, C)
        ].set(idx, mode="drop")
        a = head_pos[sid]
        b = end_pos[sid]
        slot = slot_by_seg[sid]
        row_new = new_by_seg[sid]
        row_ok = vs & (slot >= 0)
        cslot = jnp.clip(slot, 0, S - 1)
        p = idx - a  # position within the segment
        n_seg = b - a + 1

        # Reclaimed slots are handled GATHER-side: every read of a
        # newly-admitted key's old lanes is masked/ignored at the read
        # instead of a full-(slots, h) reset pass — the previous tenant's
        # values never leak, and per-chunk work stays O(C·h).  (Every
        # admitted head also lands a scatter below, so no reclaimed slot
        # keeps stale ``last``/``n_seen``.)
        #
        # All carry history comes through ONE (C, h) row gather (``crows``)
        # so the donated (slots, h) buffer has exactly two uses — that
        # gather (which feeds the scattered values) and the batched scatter
        # itself.  A second independent read (e.g. a direct warm-prefix
        # gather for ``ys``) leaves XLA unable to order the reads before
        # the in-place scatter, and copy-insertion materializes full
        # (slots, h) copies that put the K-cliff right back.

        # -- lift + intra-chunk window folds: the flip sweep ---------------
        # Per-row spans [max(a, j-W+1), j] have monotone starts AND ends
        # within each segment — the flip invariant
        # (:mod:`repro.core.event_time` module docstring).  Cutting each
        # segment into W-aligned blocks (boundary at p % W == 0) makes every
        # span exact as suffix-scan-left-of-boundary ⊗
        # prefix-scan-right-of-boundary: with p = qW + r, the span start
        # max(a, j-W+1) lands at the block start a+qW when r = W-1 or p < W
        # (prefix alone suffices) and strictly inside block q-1 otherwise
        # (its block-suffix ends exactly at the boundary).  O(1) ⊗/row —
        # replaces the old O(log W) per-row doubling range fold.
        lifted = _mask_tree(jax.vmap(m.lift)(xss), row_ok, ident)
        m_sweep = self._sweep_monoid()
        if self.horizon is not None:
            # Event-time span starts: within a segment the in-horizon rows
            # form a suffix (per-key ts non-decreasing), found by the
            # bounded finger search — row j's chunk span is
            # [max(count start, s0_j), j].  Starts stay non-decreasing
            # globally (s0 is monotone within a segment, segments are
            # disjoint and invalid rows sort last with starts = idx + 1)
            # and ends = idx is strictly increasing, so the generic flip
            # sweep applies; the W-aligned block trick below does not (its
            # exactness needs starts == max(a, j - W + 1) precisely).
            thr = tss - jnp.asarray(self.horizon, tss.dtype)
            s0 = ooo_index.seg_bounded_search(tss, a, idx, thr)
            starts = jnp.where(
                row_ok,
                jnp.maximum(jnp.maximum(a, idx - (W - 1)), s0),
                idx + 1,
            )
            if self.use_inverse:
                intra = range_fold_invertible(m_sweep, lifted, starts, idx)
            else:
                intra = flip_range_fold(m_sweep, lifted, starts, idx)
        elif self.use_inverse:
            starts = jnp.where(row_ok, jnp.maximum(a, idx - (W - 1)), idx + 1)
            intra = range_fold_invertible(m_sweep, lifted, starts, idx)
        else:
            starts = jnp.where(row_ok, jnp.maximum(a, idx - (W - 1)), idx + 1)
            # invalid rows are their own single-row segments (their lifted
            # rows are already identity), so garbage never crosses them
            bstart = seg_head | ~vs | (row_ok & (p % W == 0))
            bpref = self._seg_pscan(bstart, lifted)
            if W > C:
                # a chunk can't wrap a block: every span starts at its
                # segment head, the prefix scan alone is exact
                intra = bpref
            else:
                bend = seg_end | ~vs | (row_ok & (p % W == W - 1))
                bsuf = self._seg_scan(bend, lifted)
                cellstart = jax.lax.associative_scan(
                    jnp.maximum, jnp.where(bstart, idx, 0)
                )
                left = _take0(bsuf, jnp.clip(starts, 0, C - 1))
                both = m_sweep.combine(left, bpref)  # older operand LEFT
                intra = _where_rows(starts >= cellstart, bpref, both)

        if h > 0:
            # the ONE donated-buffer read: a contiguous (C, h) row gather
            # (two independent reads of the donated buffer break in-place
            # donation).  Every (C, h) lane realignment after it is built
            # from whole rows: the refresh's lanes min(t + n_seg, h-1) by
            # log2(h) static shifts and selects, and the from-chunk lanes
            # below by a row gather of a Hankel view.  On a TPU v5e an
            # element-index gather over (C, h) costs ~11-20 ns per element
            # (four of them took ~47 of a 63-ms step at C=4096, h=255),
            # while row and slice gathers of the same bytes are cheap.  Only
            # the (C, 1) warm-prefix lane pick stays a take_along_axis.
            # row_new rows' garbage is masked at every consumer (the
            # need_carry select below / the refresh's ``old_m`` mask).
            crows = jax.tree.map(lambda cl: cl[cslot], state["carry"])
            old = _shift_lanes(crows, n_seg, h)
            pidx = jnp.clip(p, 0, h - 1)[:, None]
            if self.horizon is not None:
                # the ONE carry_ts read (the ts mirror of ``crows``): t* =
                # #{lane_ts <= thr} is the first lane whose whole suffix is
                # in-horizon (-inf "absent" lanes always count as expired),
                # and the count cap composes with the horizon cap as
                # lane max(p, t*) — expiry is purely read-side
                ts_rows = state["carry_ts"][cslot]
                thr_col = thr[:, None]
                tstar = jnp.sum(
                    (ts_rows <= thr_col).astype(jnp.int32), axis=1
                )
                lane = jnp.maximum(pidx, jnp.clip(tstar, 0, h - 1)[:, None])
            else:
                lane = pidx
            cvals = jax.tree.map(
                lambda cr: jnp.take_along_axis(
                    cr, lane.reshape((C, 1) + (1,) * (cr.ndim - 2)), axis=1
                )[:, 0],
                crows,
            )

        # -- warm prefix: windows reaching into the key's history ----------
        if h > 0:
            need_carry = row_ok & (p < h) & ~row_new
            if self.horizon is not None:
                # history contributes only when the whole chunk span so far
                # is itself in-horizon (s0 == a; history is older than any
                # chunk row) and at least one history lane survives
                need_carry &= (s0 == a) & (tstar < h)
            warmed = m.combine(cvals, intra)
            ys = _where_rows(need_carry, warmed, intra)
        else:
            ys = intra
        ys = _mask_tree(ys, row_ok, ident)

        # -- refreshed carries: ONE batched (C, h) scatter -----------------
        # Entry t of a head's refreshed carry folds the slot's trailing
        # h - t elements: a pure segment suffix when that fits in the chunk
        # (``from_chunk``), else surviving old-carry lane t + n_seg extended
        # by the whole-segment fold.  ``from_chunk`` on lane t reads the
        # segment scan at row b - (h - t) + 1: for every row that is the
        # contiguous run of rows b - hc + 1 .. b, one row of a Hankel view
        # (:func:`_row_windows`), never a (C, hc) element-index gather.  The
        # whole-segment fold is a (C,) pick broadcast over the lanes, which
        # fuses into the select.
        if h > 0:
            ss = self._seg_scan(seg_end, lifted)
            old_m = _mask_tree(old, ~row_new, ident)
            whole = jax.tree.map(
                lambda s_: jnp.broadcast_to(
                    s_[jnp.clip(a, 0, C - 1)][:, None],
                    (C, h) + s_.shape[1:],
                ),
                ss,
            )
            carried = m.combine(old_m, whole)
            # Static lane split: entry t folds need = h - t trailing
            # elements and a C-row chunk holds n_seg <= C of them, so only
            # the last min(h, C) lanes can ever take the ``from_chunk``
            # branch — the data-dependent gather + select is skipped
            # entirely on the h - min(h, C) leading lanes (3/4 of the
            # refresh at W=4096, C=1024).
            hc = min(h, C)
            h0 = h - hc
            # (hc,) trailing elements entry t folds: hc, hc - 1, ..., 1
            need = hc - jnp.arange(hc, dtype=jnp.int32)
            in_chunk = need[None, :] <= n_seg[:, None]  # (C, hc)
            # lane t reads row b - need + 1 >= a wherever in_chunk holds, so
            # the padding before row 0 is only ever read on masked lanes
            from_chunk = _row_windows(ss, b, hc)
            new_tail = jax.tree.map(
                lambda fc, cd: jnp.where(_bc(in_chunk, fc), fc, cd[:, h0:]),
                from_chunk,
                carried,
            )
            head_scat = jnp.where(seg_head & row_ok, slot, S)
            if h0:
                # two scatters into disjoint lane ranges instead of a
                # concatenated (C, h) update: the leading-lane write streams
                # ``carried`` directly, no 16MB concat materialization
                carry1 = jax.tree.map(
                    lambda cl, cd: cl.at[head_scat, :h0].set(
                        cd[:, :h0], mode="drop"
                    ),
                    state["carry"],
                    carried,
                )
                carry1 = jax.tree.map(
                    lambda cl, nt: cl.at[head_scat, h0:].set(
                        nt, mode="drop"
                    ),
                    carry1,
                    new_tail,
                )
            else:
                carry1 = jax.tree.map(
                    lambda cl, nt: cl.at[head_scat].set(nt, mode="drop"),
                    state["carry"],
                    new_tail,
                )
            if self.horizon is not None:
                # carry_ts rides the SAME ladder as carry: shifted old lane
                # t + n_seg (-inf for new heads) on lanes the chunk can't
                # fill, the chunk's own timestamps where the trailing suffix
                # fits — the same shifts of the already-gathered ts_rows,
                # the same row slices of ``tss`` and one scatter, so the
                # donation discipline holds for carry_ts too
                ts_old = _shift_lanes(ts_rows, n_seg, h)
                ts_old = jnp.where(row_new[:, None], -jnp.inf, ts_old)
                ts_tail = jnp.where(
                    in_chunk, _row_windows(tss, b, hc), ts_old[:, h0:]
                )
                if h0:
                    cts1 = state["carry_ts"].at[head_scat, :h0].set(
                        ts_old[:, :h0], mode="drop"
                    )
                    cts1 = cts1.at[head_scat, h0:].set(ts_tail, mode="drop")
                else:
                    cts1 = state["carry_ts"].at[head_scat].set(
                        ts_tail, mode="drop"
                    )
        else:
            head_scat = jnp.where(seg_head & row_ok, slot, S)
            carry1 = state["carry"]
            if self.horizon is not None:
                cts1 = state["carry_ts"]

        # -- per-slot latest aggregate + seen counts -----------------------
        y_end = _take0(ys, jnp.clip(b, 0, C - 1))
        last1 = jax.tree.map(
            lambda ll, v: ll.at[head_scat].set(v, mode="drop"),
            state["last"],
            y_end,
        )
        n_seen1 = state["n_seen"].at[head_scat].set(
            jnp.where(row_new, 0, state["n_seen"][cslot]) + n_seg,
            mode="drop",
        )

        dropped_sorted = vs & ~row_ok
        state = dict(
            state,
            dir=dir_state,
            carry=carry1,
            last=last1,
            n_seen=n_seen1,
            tick=jnp.maximum(tick, jnp.max(jnp.where(vs, tss, -jnp.inf))),
            n_dropped=state["n_dropped"] + dropped_sorted.sum(dtype=jnp.int32),
        )
        if self.horizon is not None:
            state["carry_ts"] = cts1
        if self.ttl is not None:
            state = self.expire(state)
        info = {
            "slots": slot[inv],
            "dropped": dropped_sorted[inv],
            "n_live": dir_state["n_live"],
            "n_evicted": dir_state["n_evicted"],
        }
        return state, _take0(ys, inv), info

    # -- SWAG interop (the carry protocol across the key dimension) --------

    def export_states(self, state: PyTree, keys, algo, capacity: Optional[int] = None):
        """Per-key live SWAG states built from the stored carries via
        ``carry_to_state`` — hand a key's window to any per-element
        algorithm.  Returns ``(states, found)`` with a leading key axis."""
        capacity = capacity or self.window + 1
        keys = jnp.asarray(keys, jnp.int32)
        slot, found = self.directory.lookup(state["dir"], keys)
        carries = jax.tree.map(
            lambda cl: cl[jnp.clip(slot, 0, self.slots - 1)], state["carry"]
        )
        states = jax.vmap(
            lambda c: swag_base.carry_to_state(algo, self.monoid, c, capacity)
        )(carries)
        return states, found

    def adopt_states(self, state: PyTree, keys, swag_states, algo) -> PyTree:
        """Admit ``keys`` and seed their lanes from live per-element SWAG
        states (``state_to_carry``) — warm-start the store from existing
        windows.  Keys beyond the slot budget are dropped (directory
        ``n_failed``)."""
        keys = jnp.asarray(keys, jnp.int32)
        carries = jax.vmap(
            lambda s: swag_base.state_to_carry(algo, self.monoid, s, self.window)
        )(swag_states)
        lasts = jax.vmap(lambda s: algo.query(self.monoid, s))(swag_states)
        counts = jax.vmap(algo.size)(swag_states).astype(jnp.int32)
        tick = state["tick"] + 1.0

        def body(i, acc):
            dir_state, touched, slots = acc
            dir_state, touched, slot, _ = self.directory.admit_row(
                dir_state, touched, keys[i], tick
            )
            return dir_state, touched, slots.at[i].set(slot)

        n = int(keys.shape[0])
        dir_state, _, slots = jax.lax.fori_loop(
            0,
            n,
            body,
            (
                state["dir"],
                jnp.zeros((self.slots,), bool),
                jnp.full((n,), -1, jnp.int32),
            ),
        )
        scat = jnp.where(slots >= 0, slots, self.slots)
        state = dict(
            state,
            dir=dir_state,
            carry=jax.tree.map(
                lambda cl, cv: cl.at[scat].set(cv, mode="drop"),
                state["carry"],
                carries,
            ),
            last=jax.tree.map(
                lambda ll, lv: ll.at[scat].set(lv, mode="drop"),
                state["last"],
                lasts,
            ),
            n_seen=state["n_seen"].at[scat].set(counts, mode="drop"),
            tick=tick,
        )
        if self.horizon is not None:
            # per-element timestamps don't survive the carry protocol:
            # adopted history is stamped "arrived now", so it expires
            # all-or-nothing once ``tick`` leaves the horizon
            state["carry_ts"] = state["carry_ts"].at[scat].set(
                tick, mode="drop"
            )
        return state


# ---------------------------------------------------------------------------
# Chunk-at-a-time driver
# ---------------------------------------------------------------------------


class KeyedChunkedStream:
    """Chunked driver over a :class:`KeyedWindowStore` (jit per chunk shape,
    ragged-final-chunk padding) — the keyed counterpart of
    :class:`repro.core.chunked.ChunkedStream`.

    Usage::

        eng = KeyedChunkedStream(monoid, window=256, slots=4096, chunk=4096)
        state = eng.init_state()
        state, ys, info = eng.process_chunk(state, keys, xs)   # (C,) rows
        state, ys = eng.stream(keys, xs)                       # whole stream

    ``donate=True`` (the default) donates the state buffers into the jitted
    update, making the (slots, h) carry scatter in-place — per-chunk cost
    stays O(chunk·h) even when the resident state is huge.  The flip side:
    a state passed to :meth:`process_chunk` is CONSUMED (its buffers are
    deleted); always continue from the returned state, and pass
    ``donate=False`` when external references to the state must stay live
    (e.g. a checkpoint payload holding the same arrays).
    """

    def __init__(
        self,
        monoid: Monoid,
        window: int,
        slots: int,
        chunk: Optional[int] = None,
        *,
        donate: bool = True,
        **store_kwargs,
    ):
        self.store = KeyedWindowStore(monoid, window, slots, **store_kwargs)
        self.monoid = monoid
        self.window = self.store.window
        self.chunk = int(chunk) if chunk is not None else 1024
        self.donate = bool(donate)
        self._jitted: dict = {}
        self._full_masks: dict = {}
        # obs plumbing (all None/zero when the store's ObsConfig is off —
        # process_chunk then takes the exact pre-obs code path)
        self._obs = self.store.obs
        self._obs_snap: Optional[dict] = None
        self._obs_chunks = 0
        self._obs_rows = 0
        self._trace_stages: dict = {}
        # ONE async dispatch for the per-chunk scalar snapshot (4 separate
        # jnp.copy calls measured ~10% off keyed throughput; fused they
        # disappear into dispatch noise)
        self._snap_jit = jax.jit(lambda t: jax.tree.map(jnp.copy, t))

    def init_state(self) -> PyTree:
        return self.store.init_state()

    def _full_mask(self, C: int):
        m = self._full_masks.get(C)
        if m is None:
            m = self._full_masks[C] = jnp.ones((C,), bool)
        return m

    def process_chunk(self, state, keys, xs, ts=None, mask=None):
        """Jitted :meth:`KeyedWindowStore.update_chunk` (cached per chunk
        length and ts presence)."""
        C = int(jnp.shape(jnp.asarray(keys))[0])
        if mask is None:
            mask = self._full_mask(C)
        key = (C, ts is not None)
        fn = self._jitted.get(key)
        if fn is None:
            donate = dict(donate_argnums=(0,)) if self.donate else {}
            if ts is None:
                fn = jax.jit(
                    lambda st, k, x, mk: self.store.update_chunk(
                        st, k, x, None, mk
                    ),
                    **donate,
                )
            else:
                fn = jax.jit(self.store.update_chunk, **donate)
            self._jitted[key] = fn
        if self._obs is None:
            if ts is None:
                return fn(state, keys, xs, mask)
            return fn(state, keys, xs, ts, mask)
        return self._process_chunk_obs(fn, state, keys, xs, ts, mask, C)

    def _process_chunk_obs(self, fn, state, keys, xs, ts, mask, C):
        """The instrumented dispatch: optional trace span around the call
        (synced so the duration is real, with roofline-apportioned stage
        sub-spans), then tiny-scalar snapshot copies for scrape collectors.
        The copies matter: with donation on, the returned state's buffers
        die inside the NEXT process_chunk — a collector reading them later
        would hit deleted buffers."""
        tr = self._obs.trace
        if tr is not None:
            with tr.span("keyed.update_chunk", args={"chunk": C}) as sa:
                t0 = tr._now_us()
                out = (fn(state, keys, xs, mask) if ts is None
                       else fn(state, keys, xs, ts, mask))
                jax.block_until_ready(out[1])
                dur = tr._now_us() - t0
            stages = self._trace_stages.get(C)
            if stages is None:
                from repro.roofline.analysis import keyed_update_cost

                stages = self._trace_stages[C] = keyed_update_cost(
                    C, self.window
                )["stages"]
            tr.add_stage_spans("keyed.update_chunk", t0, dur, stages, tid=1)
        else:
            out = (fn(state, keys, xs, mask) if ts is None
                   else fn(state, keys, xs, ts, mask))
        st, _, info = out
        self._obs_chunks += 1
        self._obs_rows += C
        self._obs_snap = self._snap_jit({
            "n_live": info["n_live"],
            "n_evicted": info["n_evicted"],
            "n_failed": st["dir"]["n_failed"],
            "n_dropped": st["n_dropped"],
        })
        return out

    def attach_obs(self, registry, *, prefix: str = "repro_keyed"):
        """Register this stream's scrape collector: live/evicted/failed/
        dropped from the latest chunk's snapshot plus host-side chunk/row
        throughput counters.  Admission-branch counters ride along globally
        via ``obs.counters.admission`` (adopted by the default registry)."""
        series = {
            "n_live": (f"{prefix}_live_keys", "gauge",
                       "keys currently resident in the slot pool"),
            "n_evicted": (f"{prefix}_evictions_total", "counter",
                          "LRU + TTL evictions since init"),
            "n_failed": (f"{prefix}_admission_failed_total", "counter",
                         "admissions abandoned after probe/victim rounds"),
            "n_dropped": (f"{prefix}_dropped_rows_total", "counter",
                          "chunk rows dropped by failed admission"),
        }
        for key, (name, typ, help) in series.items():
            registry.describe(name, typ, help)
        registry.describe(f"{prefix}_chunks_total", "counter",
                          "update_chunk dispatches")
        registry.describe(f"{prefix}_rows_total", "counter",
                          "chunk rows ingested (incl. padding)")

        def collect():
            out = {
                f"{prefix}_chunks_total": self._obs_chunks,
                f"{prefix}_rows_total": self._obs_rows,
            }
            if self._obs_snap is not None:
                for key, (name, _, _) in series.items():
                    out[name] = self._obs_snap[key]
            return out

        registry.register_collector(collect)
        return collect

    def query(self, state, keys):
        return self.store.query(state, keys)

    def stream(self, keys, xs, *, ts=None, state: Optional[PyTree] = None):
        """Whole-stream ingest: (T,) keys / (T, ...) values chunk-by-chunk;
        returns ``(state, (T, ...) per-row window aggregates)``.  The ragged
        last chunk is padded under a mask so every chunk shares one
        compilation."""
        keys = jnp.asarray(keys, jnp.int32)
        T = int(keys.shape[0])
        if state is None:
            state = self.init_state()
        if T == 0:
            return state, jax.vmap(self.monoid.lift)(xs)
        ys = []
        for lo in range(0, T, self.chunk):
            hi = min(lo + self.chunk, T)
            pk = keys[lo:hi]
            px = jax.tree.map(lambda a_: a_[lo:hi], xs)
            pt = None if ts is None else jnp.asarray(ts)[lo:hi]
            if hi - lo < self.chunk:
                pad = self.chunk - (hi - lo)
                pk = jnp.concatenate([pk, jnp.broadcast_to(pk[-1:], (pad,))])
                px = jax.tree.map(
                    lambda a_: jnp.concatenate(
                        [a_, jnp.broadcast_to(a_[-1:], (pad,) + a_.shape[1:])], 0
                    ),
                    px,
                )
                if pt is not None:
                    pt = jnp.concatenate(
                        [pt, jnp.broadcast_to(pt[-1:], (pad,))]
                    )
                mask = jnp.arange(self.chunk) < (hi - lo)
                state, y, _ = self.process_chunk(state, pk, px, pt, mask)
                y = jax.tree.map(lambda a_: a_[: hi - lo], y)
            else:
                state, y, _ = self.process_chunk(state, pk, px, pt)
            ys.append(y)
        return state, jax.tree.map(
            lambda *parts: jnp.concatenate(parts, axis=0), *ys
        )


# ---------------------------------------------------------------------------
# Device sharding of the key space
# ---------------------------------------------------------------------------


def shard_of_key(keys, n_shards: int):
    """Key → shard assignment by hash (a different hash stream than the
    directory's probe hash, so shard skew does not correlate with probe
    clustering)."""
    return (_hash_u32(jnp.asarray(keys, jnp.int32), 3) % jnp.uint32(n_shards)).astype(
        jnp.int32
    )


class ShardedKeyedStore:
    """Key-space sharding of a :class:`KeyedWindowStore` over a mesh axis.

    Every shard owns ``slots`` slots and a private directory; a chunk is
    broadcast to all shards and each masks it down to its own rows
    (``hash(key) % shards == shard_index``) — the steady state runs ZERO
    collectives (no gathers, no psums: outputs and state stay shard-local,
    stacked on the leading axis).  Partition specs come from
    :func:`repro.distributed.sharding.keyed_store_pspecs`.

    Usage::

        mesh = jax.make_mesh((R,), ("data",))
        sh = ShardedKeyedStore(monoid, window, slots_per_shard, mesh, "data")
        state = sh.init_state()                       # (R, ...)-stacked
        state, ys, owner = sh.update_chunk(state, keys, xs)
        y = ShardedKeyedStore.collect(ys, owner)      # host-side select
    """

    def __init__(
        self,
        monoid: Monoid,
        window: int,
        slots_per_shard: int,
        mesh,
        axis: str = "data",
        *,
        donate: bool = True,
        **store_kwargs,
    ):
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import keyed_store_pspecs

        self.store = KeyedWindowStore(monoid, window, slots_per_shard, **store_kwargs)
        self.mesh = mesh
        self.axis = axis
        self.n_shards = mesh.shape[axis]
        self._pspecs = keyed_store_pspecs

        # two entry points: with an explicit ts chunk, and without one — the
        # latter must pass ts=None THROUGH to the store so each shard's internal
        # tick drives recency (a constant ts would freeze last_used, making
        # LRU degenerate and TTL evict actively-used keys)
        def build(has_ts):
            def local_update(st, keys, xs, *rest):
                ts_row = rest[0] if has_ts else None
                mask = rest[-1]
                idx = jax.lax.axis_index(axis)
                mine = mask & (shard_of_key(keys, self.n_shards) == idx)
                st1 = jax.tree.map(lambda a_: a_[0], st)  # drop the shard axis
                st2, ys, _info = self.store.update_chunk(
                    st1, keys, xs, ts_row, mine
                )
                return (
                    jax.tree.map(lambda a_: a_[None], st2),
                    jax.tree.map(lambda a_: a_[None], ys),
                )

            def wrapped(st, keys, xs, *rest):
                specs = jax.tree.map(lambda _: P(axis), st)
                y_spec = jax.tree.map(
                    lambda _: P(axis),
                    jax.eval_shape(lambda x: jax.vmap(monoid.lift)(x), xs),
                )
                return jax.shard_map(
                    local_update,
                    mesh=mesh,
                    in_specs=(specs, P(), P()) + (P(),) * len(rest),
                    out_specs=(specs, y_spec),
                    # the batched-admission while_loop has no varying-axes
                    # rule; every output is explicitly sharded anyway
                    check_vma=False,
                )(st, keys, xs, *rest)

            if donate:
                # state-in is consumed: the per-shard carry scatter runs
                # in-place (continue from the returned state only)
                return jax.jit(wrapped, donate_argnums=(0,))
            return jax.jit(wrapped)

        self._update_with_ts = build(True)
        self._update_no_ts = build(False)

    def init_state(self) -> PyTree:
        """The (shards, ...)-stacked state, built IN PLACE: one jitted
        program whose outputs are sharded over the mesh axis, so each device
        materializes only its own shard (the whole stack never exists on
        any single device)."""
        from repro.distributed.sharding import make_shardings

        n = self.n_shards

        def build():
            return jax.tree.map(
                lambda a_: jnp.broadcast_to(a_, (n,) + a_.shape),
                self.store.init_state(),
            )

        shardings = make_shardings(
            self.mesh, self._pspecs(jax.eval_shape(build), self.axis)
        )
        return jax.jit(build, out_shardings=shardings)()

    def update_chunk(self, state, keys, xs, ts=None, mask=None):
        """Returns ``(state, ys, owner)``: ``ys`` is (shards, C, ...) with
        row j meaningful only at ``ys[owner[j], j]``; everything else is the
        identity.  ``owner`` is the (C,) shard assignment."""
        keys = jnp.asarray(keys, jnp.int32)
        C = int(keys.shape[0])
        if mask is None:
            mask = jnp.ones((C,), bool)
        if ts is None:
            state, ys = self._update_no_ts(state, keys, xs, mask)
        else:
            ts_row = jnp.broadcast_to(jnp.asarray(ts, jnp.float32), (C,))
            state, ys = self._update_with_ts(state, keys, xs, ts_row, mask)
        return state, ys, shard_of_key(keys, self.n_shards)

    @staticmethod
    def collect(ys: PyTree, owner) -> PyTree:
        """Host-side compaction of sharded outputs: pick each row from its
        owning shard (the one cross-shard data movement, OUTSIDE the steady
        state)."""
        owner = np.asarray(owner)
        idx = np.arange(owner.shape[0])
        return jax.tree.map(lambda a_: np.asarray(a_)[owner, idx], ys)

    def counters(self, state, *, per_shard: bool = False) -> dict:
        """MESH-WIDE store counters: ``n_live`` / ``n_evicted`` /
        ``n_failed`` / ``n_dropped`` summed over every shard (each shard
        tracks only its own rows; before this rollup the per-shard scalars
        were the only view — the telemetry blind spot).  Device values; the
        reduce runs at read time, outside the steady state.  With
        ``per_shard=True`` the un-summed (shards,) arrays ride along under
        ``"per_shard"``."""
        d = state["dir"]
        shard_vals = {
            "n_live": d["n_live"],
            "n_evicted": d["n_evicted"],
            "n_failed": d["n_failed"],
            "n_dropped": state["n_dropped"],
        }
        out = {k: v.sum() for k, v in shard_vals.items()}
        if per_shard:
            out["per_shard"] = shard_vals
        return out

    def attach_obs(self, registry, get_state, *,
                   prefix: str = "repro_sharded"):
        """Register a scrape collector over ``get_state()`` (the caller's
        current state variable): mesh-wide totals plus per-shard
        ``{shard="i"}``-labelled series."""
        series = {
            "n_live": (f"{prefix}_live_keys", "gauge",
                       "keys resident across all shards"),
            "n_evicted": (f"{prefix}_evictions_total", "counter",
                          "LRU + TTL evictions, all shards"),
            "n_failed": (f"{prefix}_admission_failed_total", "counter",
                         "abandoned admissions, all shards"),
            "n_dropped": (f"{prefix}_dropped_rows_total", "counter",
                          "rows dropped by failed admission, all shards"),
        }
        for key, (name, typ, help) in series.items():
            registry.describe(name, typ, help)

        def collect():
            c = self.counters(get_state(), per_shard=True)
            # the (shards,) arrays are sharded over the mesh: index them on
            # the host (one transfer), not with a device gather
            per = jax.device_get(c.pop("per_shard"))
            out = {}
            for key, (name, _, _) in series.items():
                out[name] = c[key]
                for i in range(self.n_shards):
                    out[f'{name}{{shard="{i}"}}'] = per[key][i]
            return out

        registry.register_collector(collect)
        return collect
