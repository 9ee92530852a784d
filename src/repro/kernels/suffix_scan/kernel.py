"""Pallas TPU kernel: batched suffix scan — the Two-Stacks *flip* in bulk.

``y[b, t] = x[b, t] ⊗ … ⊗ x[b, T-1]``: exactly the in-place reversal loop of
Two-Stacks Lite's flip (paper §4 lines 11–14) / the front-stack rebuild of
Two-Stacks (§3), vectorized over B rows.  Used for bulk evictions and for
building the "front stack" aggregates of a coarse-grained window in one pass.

Tiling: grid ``(B/Bt, T/Tb)``; the sequence-block axis is innermost and
iterated in REVERSE via the index_map (blocks right→left), with a per-row
carry aggregate in a ``(Bt, 1)`` VMEM scratch:

    carry ← 1                         at j = 0 (rightmost block)
    S     ← in-block suffix scan(X) ⊗ carry
    carry ← S[:, 0]                   (whole block ⊗ old carry)

In-block scan is Hillis–Steele (⌈log₂ Tb⌉ shift-combines on VPU lanes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops_registry import combine_fn, identity_for
from repro.kernels.sliding_window.kernel import _suffix_scan_block


def _suffix_kernel(x_ref, o_ref, carry_ref, *, op: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jnp.full(
            carry_ref.shape, identity_for(op, x_ref.dtype), x_ref.dtype
        )

    x = x_ref[...]
    s = _suffix_scan_block(x, op)
    s = combine_fn(op)(s, carry_ref[...])  # carry is strictly newer → RIGHT
    o_ref[...] = s
    carry_ref[...] = s[:, 0:1]


@functools.partial(
    jax.jit, static_argnames=("op", "block_b", "block_t", "interpret")
)
def suffix_scan_pallas(
    x: jax.Array,
    *,
    op: str = "sum",
    block_b: int = 8,
    block_t: int = 256,
    interpret: bool,
) -> jax.Array:
    """Row-wise inclusive suffix scan of (B, T) with monoid ``op``."""
    if x.ndim != 2:
        raise ValueError(f"expected (B, T), got {x.shape}")
    B, T = x.shape
    ident = identity_for(op, x.dtype)

    Bt = min(block_b, B)
    Tb = min(block_t, T)
    B_pad = math.ceil(B / Bt) * Bt
    T_pad = math.ceil(T / Tb) * Tb
    xp = jnp.full((B_pad, T_pad), ident, x.dtype).at[:B, :T].set(x)

    n_tb = T_pad // Tb
    out = pl.pallas_call(
        functools.partial(_suffix_kernel, op=op),
        grid=(B_pad // Bt, n_tb),
        in_specs=[pl.BlockSpec((Bt, Tb), lambda b, j: (b, n_tb - 1 - j))],
        out_specs=pl.BlockSpec((Bt, Tb), lambda b, j: (b, n_tb - 1 - j)),
        out_shape=jax.ShapeDtypeStruct((B_pad, T_pad), x.dtype),
        scratch_shapes=[pltpu.VMEM((Bt, 1), x.dtype)],
        interpret=interpret,
    )(xp)
    return out[:B, :T]
