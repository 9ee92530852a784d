"""Pallas TPU kernel: fused chunked gated-linear-attention (GLA / SSD).

The §Perf conclusion for rwkv6 × train_4k: its roofline gap is pure memory
traffic — the unfused chunked-GLA chain (cumulative decays, normalized keys,
score matrices, state read/write) round-trips HBM between every op.  This
kernel fuses one chunk's entire computation into a single VMEM-resident body
and carries the (K, V) recurrent state in VMEM scratch across the sequential
chunk grid — the state never touches HBM between chunks.

Math per chunk (length L, Mamba-2 / inclusive-read convention):
    P_t   = ∏_{{j≤t}} a_j                       (cumulative decay, in-chunk)
    o_t   = (r_t ⊙ P_t)·S₀ + Σ_{{j≤t}} [(r_t⊙P_t)·(k_j/P_j)] v_j
    S_L   = P_L ⊙ S₀ + Σ_j ((P_L/P_j) ⊙ k_j) ⊗ v_j

Grid ``(B·H, T/L)`` — the chunk axis is innermost/sequential, state scratch
``(K, V)`` f32 persists across it (same carry pattern as kernels/suffix_scan).
Inputs are blocked as (1, L, K|V) VMEM tiles.  MXU does the three einsums;
the decay cumprod is a log-space cumsum on VPU lanes.

RWKV's pre-decay read + bonus-u variant differs only in using P_{{t-1}}, a
strict mask, and a diag(u) self term — exposed via ``variant=\"rwkv\"`` (the
bonus vector is passed as an extra (1, K) operand).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gla_kernel(r_ref, k_ref, v_ref, a_ref, u_ref, o_ref, s_ref,
                *, variant: str, L: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)

    r = r_ref[0].astype(jnp.float32)  # (L, K)
    k = k_ref[0].astype(jnp.float32)  # (L, K)
    v = v_ref[0].astype(jnp.float32)  # (L, V)
    a = a_ref[0].astype(jnp.float32)  # (L, K)

    logp = jnp.cumsum(jnp.log(jnp.maximum(a, 1e-12)), axis=0)  # (L, K)
    P = jnp.exp(logp)
    k_n = k / jnp.maximum(P, 1e-24)

    if variant == "rwkv":
        P_read = jnp.exp(logp - jnp.log(jnp.maximum(a, 1e-12)))  # P_{t-1}
        mask = jnp.tril(jnp.ones((L, L), jnp.float32), k=-1)
    else:
        P_read = P
        mask = jnp.tril(jnp.ones((L, L), jnp.float32))

    r_t = r * P_read  # (L, K)
    s0 = s_ref[...]  # (K, V) f32, VMEM-resident across chunks
    inter = jnp.dot(r_t, s0, preferred_element_type=jnp.float32)  # (L, V)
    scores = jnp.dot(r_t, k_n.T, preferred_element_type=jnp.float32)  # (L, L)
    scores = scores * mask
    intra = jnp.dot(scores, v, preferred_element_type=jnp.float32)  # (L, V)
    o = inter + intra
    if variant == "rwkv":
        u = u_ref[0].astype(jnp.float32)  # (1, K) bonus
        s_self = jnp.sum(r * u * k, axis=1, keepdims=True)  # (L, 1)
        o = o + s_self * v

    PL = P[-1:]  # (1, K)
    s_ref[...] = PL.T * s0 + jnp.dot(
        (k_n * PL).T, v, preferred_element_type=jnp.float32
    )
    o_ref[0] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "variant", "interpret")
)
def gla_pallas(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    a: jax.Array,
    bonus_u: jax.Array | None = None,
    *,
    chunk: int = 64,
    variant: str = "mamba",
    interpret: bool,
) -> jax.Array:
    """Fused chunked GLA.  r,k,a: (B,T,H,K); v: (B,T,H,V) → (B,T,H,V).

    Zero initial state (add an inter-chunk prologue chunk to seed one).
    """
    B, T, H, K = r.shape
    V = v.shape[-1]
    L = min(chunk, T)
    Tp = math.ceil(T / L) * L

    def prep(x, fill=0.0):
        if Tp != T:
            x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0), (0, 0)),
                        constant_values=fill)
        # (B,T,H,·) → (B·H, T, ·)
        return x.transpose(0, 2, 1, 3).reshape(B * H, Tp, x.shape[-1])

    rf, kf, vf = prep(r), prep(k), prep(v)
    af = prep(a, fill=1.0)
    if bonus_u is None:
        uf = jnp.zeros((B * H, 1, K), r.dtype)
    else:  # (H, K) → per (b,h) row
        uf = jnp.broadcast_to(bonus_u[None], (B, H, K)).reshape(B * H, 1, K)

    nc = Tp // L
    out = pl.pallas_call(
        functools.partial(_gla_kernel, variant=variant, L=L),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, L, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, L, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, L, V), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, L, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, K), lambda bh, c: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, L, V), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tp, V), v.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, af, uf)
    out = out.reshape(B, H, Tp, V).transpose(0, 2, 1, 3)
    return out[:, :T]
