"""Pallas TPU kernel: dense sliding-window aggregation (VHGW / two-stacks-in-space).

Computes ``y[b, t] = x[b, t-w+1] ⊗ … ⊗ x[b, t]`` (front-truncated) for an
associative ⊗ in **3 combines per element independent of w** — the van
Herk–Gil–Werman scheme, which is exactly the paper's two-stacks decomposition
applied spatially (DESIGN.md §2.2):

  * pad the front with w identities → X' of length T + w,
  * per w-sized block of X': suffix scan S (the "front stack" aggregates) and
    prefix scan P (the "back stack" aggregates),
  * y[t] = S[t+1] ⊗ P[t+w]  — one stitch across the block boundary, the
    dense analogue of ``query() = Π_F ⊗ Π_B``.

Tiling: grid ``(B/Bt, T/w)``.  Output block ``(Bt, w)`` at ``(b, j)`` reads
two input blocks of X': block ``j`` (for S) and block ``j+1`` (for P) — both
``(Bt, w)`` resident in VMEM.  In-block scans are Hillis–Steele with
⌈log₂ w⌉ unrolled shift-combine steps on VPU lanes; no MXU use, the kernel is
bandwidth-bound by design (3 streams: 2 reads + 1 write).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Op tables live in the shared registry so every kernel and the chunked
# streaming engine agree on combine/identity; re-exported here for back-compat.
from repro.kernels.ops_registry import combine_fn, identity_for


def _shift_left(x: jax.Array, d: int, fill) -> jax.Array:
    """x[:, i] ← x[:, i+d], filling the tail with ``fill`` (identity)."""
    tail = jnp.full((x.shape[0], d), fill, x.dtype)
    return jnp.concatenate([x[:, d:], tail], axis=1)


def _shift_right(x: jax.Array, d: int, fill) -> jax.Array:
    head = jnp.full((x.shape[0], d), fill, x.dtype)
    return jnp.concatenate([head, x[:, :-d]], axis=1)


def _suffix_scan_block(x: jax.Array, op: str):
    """In-block inclusive suffix scan: S[i] = x[i] ⊗ … ⊗ x[-1]."""
    comb = combine_fn(op)
    ident = identity_for(op, x.dtype)
    w = x.shape[1]
    d = 1
    while d < w:
        x = comb(x, _shift_left(x, d, ident))
        d *= 2
    return x


def _prefix_scan_block(x: jax.Array, op: str):
    """In-block inclusive prefix scan: P[i] = x[0] ⊗ … ⊗ x[i]."""
    comb = combine_fn(op)
    ident = identity_for(op, x.dtype)
    w = x.shape[1]
    d = 1
    while d < w:
        x = comb(_shift_right(x, d, ident), x)
        d *= 2
    return x


def _vhgw_kernel(xa_ref, xb_ref, o_ref, *, op: str):
    xa = xa_ref[...]  # X' block j   : windows' left fragments  (suffix scan)
    xb = xb_ref[...]  # X' block j+1 : windows' right fragments (prefix scan)
    s = _suffix_scan_block(xa, op)
    p = _prefix_scan_block(xb, op)
    ident = identity_for(op, xa.dtype)
    # y[i] = S[i+1] ⊗ P[i]; at i = w-1 the shifted S is identity and the
    # window is exactly block j+1's prefix — identity-combine keeps it exact.
    o_ref[...] = combine_fn(op)(_shift_left(s, 1, ident), p)


@functools.partial(jax.jit, static_argnames=("window", "op", "block_b", "interpret"))
def sliding_window_pallas(
    x: jax.Array,
    *,
    window: int,
    op: str = "sum",
    block_b: int = 8,
    interpret: bool,
) -> jax.Array:
    """Dense sliding-window aggregation over the last axis of ``x`` (B, T)."""
    if x.ndim != 2:
        raise ValueError(f"expected (B, T), got {x.shape}")
    B, T = x.shape
    w = int(window)
    if w <= 1:
        return x

    ident = identity_for(op, x.dtype)
    # Front-pad w identities; right-pad T to a multiple of w.
    T_pad = math.ceil(T / w) * w
    xp = jnp.full((B, T_pad + w), ident, x.dtype).at[:, w : w + T].set(x)
    Bt = min(block_b, B)
    B_pad = math.ceil(B / Bt) * Bt
    if B_pad != B:
        xp = jnp.concatenate(
            [xp, jnp.full((B_pad - B, T_pad + w), ident, x.dtype)], axis=0
        )

    grid = (B_pad // Bt, T_pad // w)
    out = pl.pallas_call(
        functools.partial(_vhgw_kernel, op=op),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Bt, w), lambda b, j: (b, j)),      # X' block j
            pl.BlockSpec((Bt, w), lambda b, j: (b, j + 1)),  # X' block j+1
        ],
        out_specs=pl.BlockSpec((Bt, w), lambda b, j: (b, j)),
        out_shape=jax.ShapeDtypeStruct((B_pad, T_pad), x.dtype),
        interpret=interpret,
    )(xp, xp)
    return out[:B, :T]
