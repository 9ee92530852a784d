"""Windowed SSM / linear-attention state via DABA Lite (beyond-paper feature).

Gated-linear recurrences (RWKV-6, Mamba-2/SSD, GLA, ...) update a state
``s_t = d_t ⊙ s_{t-1} + u_t`` where ``d_t`` is a data-dependent decay and
``u_t`` an outer-product update (kᵀv).  Each token therefore contributes an
*affine map*; affine maps compose associatively, non-commutatively, and are
non-invertible when any decay channel underflows to 0 — precisely the monoid
class the paper targets.

A **sliding window of W tokens** of such a recurrence is the composition of
the last W affine maps applied to s₀ = 0.  Naively recomputing it costs
O(W) per token; inverting the decay is numerically catastrophic (divide by
d ≈ 0).  DABA Lite maintains it *exactly* in worst-case O(1) combines per
token — an evicting, bounded-context decode state with uniform per-token
latency.  This powers the ``long_500k`` decode path for rwkv6-1.6b and
zamba2-1.2b (DESIGN.md §3, §5).

Shapes: the affine element is ``{"d": (H, K, 1), "u": (H, K, V)}`` broadcast
so that composition is elementwise on decay and a decay-scaled add on state
(K = key/state dim, V = value dim, H = heads).  For Mamba-2, d is scalar per
head: shape (H, 1, 1).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import daba_lite, swag_base
from repro.core.monoids import Monoid, affine_monoid

PyTree = Any


@dataclasses.dataclass(frozen=True)
class WindowedStateCell:
    """Sliding-window recurrence cell: y_t reads the state of the last W tokens.

    Usage (decode loop, one token per call):

        cell  = WindowedStateCell(heads=H, key_dim=K, value_dim=V, window=W)
        state = cell.init()
        state, s_win = cell.update(state, decay, update)   # s_win: (H, K, V)

    ``decay``: (H, K, 1) or broadcastable — per-channel decay d_t in [0, 1].
    ``update``: (H, K, V) — the additive update u_t (e.g. k_tᵀ v_t).
    ``s_win`` is EXACTLY sum_{i=t-W+1..t} (prod_{j>i} d_j) u_i — the state a
    fresh recurrence started W tokens ago would have.  Worst-case 3 combines
    per token (Theorem 13), independent of W.
    """

    heads: int
    key_dim: int
    value_dim: int
    window: int

    @property
    def monoid(self) -> Monoid:
        base = affine_monoid((self.heads, self.key_dim, self.value_dim))

        # Decay is stored broadcast-shaped (H, K, 1) to avoid materializing a
        # (H, K, V) decay; combine broadcasts it over the value dim.
        def identity():
            return {
                "d": jnp.ones((self.heads, self.key_dim, 1), jnp.float32),
                "u": jnp.zeros((self.heads, self.key_dim, self.value_dim), jnp.float32),
            }

        def combine(a, b):
            return {"d": a["d"] * b["d"], "u": b["d"] * a["u"] + b["u"]}

        def lift(e):
            return {"d": e["d"], "u": e["u"]}

        return dataclasses.replace(
            base, identity=identity, combine=combine, lift=lift,
            name=f"affine_h{self.heads}k{self.key_dim}v{self.value_dim}",
        )

    def init(self) -> PyTree:
        # capacity = window + 1: ring slack for the insert-then-evict step.
        return daba_lite.init(self.monoid, self.window + 1)

    # jitted with the (frozen, hashable) cell static: an eager call then
    # reuses one compiled step, where an eager lax.cond over fresh lambdas
    # traces and compiles both branches again on every token
    @functools.partial(jax.jit, static_argnums=0)
    def update(self, state: PyTree, decay: jax.Array, update: jax.Array):
        m = self.monoid
        state = daba_lite.insert(m, state, {"d": decay, "u": update})
        state = jax.lax.cond(
            daba_lite.size(state) > self.window,
            lambda s: daba_lite.evict(m, s),
            lambda s: s,
            state,
        )
        agg = daba_lite.query(m, state)
        return state, agg["u"]  # window map applied to s0 = 0

    def prefill(self, state: PyTree, decays: jax.Array, updates: jax.Array):
        """Scan a (T, …) chunk through the cell; returns (state, (T,H,K,V))."""

        def step(st, du):
            d, u = du
            return self.update(st, d, u)

        return jax.lax.scan(step, state, (decays, updates))


def reference_windowed_state(decays: jax.Array, updates: jax.Array, window: int):
    """O(T·W) oracle: for each t, run the recurrence fresh over the last W
    tokens.  decays: (T, H, K, 1); updates: (T, H, K, V) → (T, H, K, V)."""
    T = updates.shape[0]
    outs = []
    for t in range(T):
        lo = max(0, t - window + 1)
        s = jnp.zeros_like(updates[0])
        for j in range(lo, t + 1):
            s = decays[j] * s + updates[j]
        outs.append(s)
    return jnp.stack(outs)


@dataclasses.dataclass(frozen=True)
class ChunkedWindowedStateCell:
    """Coarse-grained windowed recurrence: DABA Lite over CHUNK aggregates.

    For very long windows (long_500k decode), storing one affine map per
    token would need W·(H·K·V) floats — the paper's n+2 space bound with a
    huge element type.  The paper's §8.2 coarse-grained sliding (Scotty-
    style pre-aggregation) composes: tokens accumulate into a *running
    chunk map*; every ``chunk`` tokens the completed chunk's map is inserted
    into a DABA Lite window of ``window_chunks`` elements and the oldest
    chunk is evicted.  The queryable state covers the last
    ``window_chunks·chunk ± chunk`` tokens — exact at chunk granularity,
    worst-case O(1) combines per token (DABA ops only fire at boundaries,
    and each is itself O(1) — no latency spike at chunk turnover, unlike a
    Two-Stacks flip which would recompute the whole window).
    """

    heads: int
    key_dim: int
    value_dim: int
    chunk: int
    window_chunks: int

    @property
    def monoid(self) -> Monoid:
        return WindowedStateCell(
            self.heads, self.key_dim, self.value_dim, 1
        ).monoid

    def init(self) -> PyTree:
        m = self.monoid
        return {
            "daba": daba_lite.init(m, self.window_chunks + 1),
            "partial": m.identity(),  # running (incomplete) chunk map
            "count": jnp.zeros((), jnp.int32),  # tokens in partial chunk
        }

    @functools.partial(jax.jit, static_argnums=0)  # as WindowedStateCell
    def update(self, state: PyTree, decay: jax.Array, update: jax.Array):
        m = self.monoid
        partial = m.combine(state["partial"], {"d": decay, "u": update})
        count = state["count"] + 1

        def rollover(args):
            daba, partial = args
            daba = daba_lite.insert(m, daba, partial)
            daba = jax.lax.cond(
                daba_lite.size(daba) > self.window_chunks,
                lambda s: daba_lite.evict(m, s),
                lambda s: s,
                daba,
            )
            return daba, m.identity()

        daba, partial = jax.lax.cond(
            count >= self.chunk,
            rollover,
            lambda args: args,
            (state["daba"], partial),
        )
        count = jnp.where(state["count"] + 1 >= self.chunk, 0, count)
        win = daba_lite.query(m, daba)
        eff = m.combine(win, partial)  # window ∘ current partial chunk
        new_state = {"daba": daba, "partial": partial, "count": count}
        return new_state, eff["u"]

    def prefill(self, state: PyTree, decays: jax.Array, updates: jax.Array):
        """Consume a (T, …) chunk of tokens in bulk; returns (state, (T,H,K,V)).

        The vectorized long-context prefill path (rwkv6 / zamba2): instead of
        a per-token scan, whole chunks are composed with log-depth prefix
        scans, the chunk-granular window comes from one generic VHGW sliding
        window over the chunk maps, and the final DABA Lite state is rebuilt
        through the bulk-op protocol (``insert_bulk``).  Output matches the
        sequential ``update`` loop up to float reassociation.

        Requires a fresh state (``init()``); falls back to the per-token scan
        when the state is warm or traced.
        """
        try:
            fresh = int(state["count"]) == 0 and int(
                daba_lite.size(state["daba"])
            ) == 0
        except (jax.errors.TracerArrayConversionError, jax.errors.ConcretizationTypeError):
            fresh = False
        if not fresh:
            def step(st, du):
                d, u = du
                return self.update(st, d, u)

            return jax.lax.scan(step, state, (decays, updates))

        from repro.core.chunked import tree_sliding_window  # local: avoid cycle

        m = self.monoid
        T, C, Wc = decays.shape[0], self.chunk, self.window_chunks
        n_full, rem = divmod(T, C)
        lifted = {"d": decays, "u": updates}
        ident = m.identity()

        outs = []
        win = None  # truncated chunk-window aggregates, (n_full, ...)
        if n_full:
            blocks = jax.tree.map(
                lambda a: a[: n_full * C].reshape((n_full, C) + a.shape[1:]),
                lifted,
            )
            intra = jax.lax.associative_scan(m.combine, blocks, axis=1)
            maps = jax.tree.map(lambda a: a[:, -1], intra)  # per-chunk totals
            win = tree_sliding_window(m, maps, Wc)
            win_shift = jax.tree.map(
                lambda w_, i: jnp.concatenate([i[None], w_[:-1]], axis=0),
                win,
                jax.tree.map(jnp.asarray, ident),
            )
            # token t in chunk c sees: window over chunks < c, then its own
            # running partial — except the chunk's last token, which sees the
            # just-rolled-over window (partial resets to identity there).
            full = jax.vmap(m.combine)(win_shift, intra)
            full = jax.tree.map(lambda a, w_: a.at[:, -1].set(w_), full, win)
            outs.append(
                jax.tree.map(lambda a: a.reshape((n_full * C,) + a.shape[2:]), full)
            )
        partial, count = ident, jnp.zeros((), jnp.int32)
        if rem:
            tail = jax.tree.map(lambda a: a[n_full * C:], lifted)
            p_rem = swag_base.chunk_prefix_scan(m, tail)
            w_last = (
                swag_base.tree_index(win, n_full - 1) if n_full else ident
            )
            outs.append(jax.vmap(m.combine, in_axes=(None, 0))(w_last, p_rem))
            partial, count = swag_base.tree_index(p_rem, rem - 1), jnp.asarray(rem, jnp.int32)
        out = jax.tree.map(lambda *ps: jnp.concatenate(ps, axis=0), *outs)

        daba = daba_lite.init(m, Wc + 1)
        k = min(Wc, n_full)
        if k:
            daba = daba_lite.insert_bulk(
                m, daba, jax.tree.map(lambda a: a[n_full - k:], maps)
            )
        state = {"daba": daba, "partial": partial, "count": count}
        return state, out["u"]
