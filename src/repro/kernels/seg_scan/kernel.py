"""Pallas TPU kernels: batched SEGMENTED suffix AND prefix scans.

Suffix: ``y[b, t] = x[b, t] ⊗ … ⊗ x[b, e(t)]`` where ``e(t)`` is the first
index ``≥ t`` with ``flags[b, e(t)] = True`` (the end of t's segment), or
``T-1`` when the last segment never closes.  Prefix (the mirror):
``y[b, t] = x[b, s(t)] ⊗ … ⊗ x[b, t]`` with ``flags`` marking segment
STARTS.  Together they are the two halves of the flip sweep in
:meth:`repro.core.keyed.KeyedWindowStore.update_chunk`: one key-sorted chunk
holds many segments (one per key) and every per-row window fold is one
suffix-scan value ⊗ one prefix-scan value — the keyed generalization of the
Two-Stacks flip that ``kernels/suffix_scan`` computes for a single window
(flip invariant: ``repro.core.event_time`` module docstring).

Tiling mirrors ``suffix_scan``: grid ``(B/Bt, T/Tb)``, sequence-block axis
innermost and iterated in REVERSE via the index_map (blocks right→left),
with a per-row carry in a ``(Bt, 1)`` VMEM scratch.  The carry is the
finished scan value at the right block's leftmost column — exactly the fold
any unterminated segment of the current block continues into:

    carry ← 1                                   at j = 0 (rightmost block)
    (V,F) ← in-block segmented suffix scan      (Hillis–Steele on pairs)
    O     ← F ? V : V ⊗ carry
    carry ← O[:, 0]

The in-block scan runs ⌈log₂ Tb⌉ shift-combine steps on the classic
segmented-scan pair operator ``(f_a, v_a) • (f_b, v_b) =
(f_a | f_b, f_a ? v_a : v_a ⊗ v_b)`` (left operand newer), the same
operator :func:`repro.core.keyed.seg_suffix_scan` feeds to
``associative_scan`` — so outputs agree combine-for-combine with the lax
path for every op in the registry.

Padding: values pad with the op identity and flags pad with False, so
padded columns fold identities into the carry chain without perturbing any
real segment.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops_registry import combine_fn, identity_for
from repro.kernels.sliding_window.kernel import _shift_left, _shift_right


def _seg_suffix_scan_block(v: jax.Array, f: jax.Array, op: str):
    """In-block segmented suffix scan on (value, end-flag) pairs:
    ``V[i] = x[i] ⊗ … ⊗ x[min(e(i), Tb-1)]``, ``F[i] = e(i) < Tb``."""
    comb = combine_fn(op)
    ident = identity_for(op, v.dtype)
    w = v.shape[1]
    d = 1
    while d < w:
        vs = _shift_left(v, d, ident)
        fs = _shift_left(f, d, 0)
        v = jnp.where(f != 0, v, comb(v, vs))
        f = f | fs
        d *= 2
    return v, f


def _seg_suffix_kernel(x_ref, f_ref, o_ref, carry_ref, *, op: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jnp.full(
            carry_ref.shape, identity_for(op, x_ref.dtype), x_ref.dtype
        )

    v, f = _seg_suffix_scan_block(x_ref[...], f_ref[...], op)
    # unterminated rows continue into the (strictly newer → RIGHT) carry
    out = jnp.where(f != 0, v, combine_fn(op)(v, carry_ref[...]))
    o_ref[...] = out
    carry_ref[...] = out[:, 0:1]


@functools.partial(
    jax.jit, static_argnames=("op", "block_b", "block_t", "interpret")
)
def seg_suffix_scan_pallas(
    x: jax.Array,
    flags: jax.Array,
    *,
    op: str = "sum",
    block_b: int = 8,
    block_t: int = 256,
    interpret: bool,
) -> jax.Array:
    """Row-wise segmented inclusive suffix scan of (B, T) with monoid
    ``op``; ``flags`` (B, T) marks segment ENDS."""
    if x.ndim != 2:
        raise ValueError(f"expected (B, T), got {x.shape}")
    if flags.shape != x.shape:
        raise ValueError(f"flags {flags.shape} != values {x.shape}")
    B, T = x.shape
    ident = identity_for(op, x.dtype)

    Bt = min(block_b, B)
    Tb = min(block_t, T)
    B_pad = math.ceil(B / Bt) * Bt
    T_pad = math.ceil(T / Tb) * Tb
    xp = jnp.full((B_pad, T_pad), ident, x.dtype).at[:B, :T].set(x)
    fp = (
        jnp.zeros((B_pad, T_pad), jnp.int32)
        .at[:B, :T]
        .set(flags.astype(jnp.int32))
    )

    n_tb = T_pad // Tb
    out = pl.pallas_call(
        functools.partial(_seg_suffix_kernel, op=op),
        grid=(B_pad // Bt, n_tb),
        in_specs=[
            pl.BlockSpec((Bt, Tb), lambda b, j: (b, n_tb - 1 - j)),
            pl.BlockSpec((Bt, Tb), lambda b, j: (b, n_tb - 1 - j)),
        ],
        out_specs=pl.BlockSpec((Bt, Tb), lambda b, j: (b, n_tb - 1 - j)),
        out_shape=jax.ShapeDtypeStruct((B_pad, T_pad), x.dtype),
        scratch_shapes=[pltpu.VMEM((Bt, 1), x.dtype)],
        interpret=interpret,
    )(xp, fp)
    return out[:B, :T]


def _seg_prefix_scan_block(v: jax.Array, f: jax.Array, op: str):
    """In-block segmented prefix scan on (value, start-flag) pairs:
    ``V[i] = x[max(s(i), 0)] ⊗ … ⊗ x[i]``, ``F[i] = s(i) >= 0`` (the
    segment start is inside this block)."""
    comb = combine_fn(op)
    ident = identity_for(op, v.dtype)
    w = v.shape[1]
    d = 1
    while d < w:
        vs = _shift_right(v, d, ident)
        fs = _shift_right(f, d, 0)
        v = jnp.where(f != 0, v, comb(vs, v))
        f = f | fs
        d *= 2
    return v, f


def _seg_prefix_kernel(x_ref, f_ref, o_ref, carry_ref, *, op: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jnp.full(
            carry_ref.shape, identity_for(op, x_ref.dtype), x_ref.dtype
        )

    v, f = _seg_prefix_scan_block(x_ref[...], f_ref[...], op)
    # rows whose segment started left of this block continue the (strictly
    # older → LEFT) carry
    out = jnp.where(f != 0, v, combine_fn(op)(carry_ref[...], v))
    o_ref[...] = out
    carry_ref[...] = out[:, -1:]


@functools.partial(
    jax.jit, static_argnames=("op", "block_b", "block_t", "interpret")
)
def seg_prefix_scan_pallas(
    x: jax.Array,
    flags: jax.Array,
    *,
    op: str = "sum",
    block_b: int = 8,
    block_t: int = 256,
    interpret: bool,
) -> jax.Array:
    """Row-wise segmented inclusive prefix scan of (B, T) with monoid
    ``op``; ``flags`` (B, T) marks segment STARTS.  Mirror of
    :func:`seg_suffix_scan_pallas`: forward block order, carry = finished
    scan value at the left block's rightmost column."""
    if x.ndim != 2:
        raise ValueError(f"expected (B, T), got {x.shape}")
    if flags.shape != x.shape:
        raise ValueError(f"flags {flags.shape} != values {x.shape}")
    B, T = x.shape
    ident = identity_for(op, x.dtype)

    Bt = min(block_b, B)
    Tb = min(block_t, T)
    B_pad = math.ceil(B / Bt) * Bt
    T_pad = math.ceil(T / Tb) * Tb
    xp = jnp.full((B_pad, T_pad), ident, x.dtype).at[:B, :T].set(x)
    fp = (
        jnp.zeros((B_pad, T_pad), jnp.int32)
        .at[:B, :T]
        .set(flags.astype(jnp.int32))
    )

    n_tb = T_pad // Tb
    out = pl.pallas_call(
        functools.partial(_seg_prefix_kernel, op=op),
        grid=(B_pad // Bt, n_tb),
        in_specs=[
            pl.BlockSpec((Bt, Tb), lambda b, j: (b, j)),
            pl.BlockSpec((Bt, Tb), lambda b, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((Bt, Tb), lambda b, j: (b, j)),
        out_shape=jax.ShapeDtypeStruct((B_pad, T_pad), x.dtype),
        scratch_shapes=[pltpu.VMEM((Bt, 1), x.dtype)],
        interpret=interpret,
    )(xp, fp)
    return out[:B, :T]
