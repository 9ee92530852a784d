"""Pallas TPU kernel: sliding-window (local) causal flash attention.

The attention-side consumer of the framework's windowed-aggregation story:
gemma2-27b's local layers and zamba2's shared attention block at long context
attend only to the last ``window`` positions — the KV ring buffer is the
attention analogue of the paper's FIFO window (insert at back, evict at
front), and this kernel computes the windowed softmax over it.

Flash-style online softmax.  Grid ``(B·H, T/bq, nkv)`` with
``nkv = window/bk + 1`` KV blocks per query block (the diagonal plus the
window's reach).  The KV block index is ``qj - (nkv-1) + jk``; negative
indices are clamped for the load and *masked* in-kernel (the unclamped value
is re-derived from program ids, so clamp-duplicated blocks contribute
nothing).  Running (m, l, acc) in f32 VMEM scratch; the output block is
revisited across the innermost grid axis and finalized at ``jk = nkv-1``.

Supports gemma2's logit soft-capping (``cap · tanh(s / cap)``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1.0e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, window: int, nkv: int, bq: int, bk: int, scale: float, softcap: float,
):
    qj = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    kvj = qj - (nkv - 1) + jk  # unclamped KV block index (may be < 0)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0].astype(jnp.float32)                  # (bk, D)

    s = q @ k.T                                       # (bq, bk)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    qpos = qj * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kvj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (kpos >= 0) & (kpos <= qpos) & (kpos > qpos - window)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + p @ v
    m_ref[...] = m_new

    @pl.when(jk == nkv - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "block_q", "block_k", "interpret"),
)
def local_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    """Windowed causal attention.  q, k, v: (BH, T, D) with equal heads."""
    BH, T, D = q.shape
    bq = min(block_q, T)
    bk = min(block_k, T)
    T_pad = math.ceil(T / max(bq, bk)) * max(bq, bk)
    bq = min(bq, T_pad)
    bk = min(bk, T_pad)

    def pad(x):
        if T_pad == T:
            return x
        return jnp.pad(x, ((0, 0), (0, T_pad - T), (0, 0)))

    q, k, v = pad(q), pad(k), pad(v)
    nkv = min(math.ceil(window / bk) + 1, T_pad // bk)
    n_q = T_pad // bq
    scale = 1.0 / math.sqrt(D)

    def kv_index(bh, qj, jk):
        kvj = qj - (nkv - 1) + jk
        return (bh, jnp.maximum(kvj, 0), 0)

    out = pl.pallas_call(
        functools.partial(
            _attn_kernel,
            window=window, nkv=nkv, bq=bq, bk=bk, scale=scale, softcap=softcap,
        ),
        grid=(BH, n_q, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qj, jk: (bh, qj, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qj, jk: (bh, qj, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :T, :]
