"""Public op: sliding-window causal attention with GQA and dispatch.

``local_attention(q, k, v, window, ...)`` takes (B, Hq, T, D) queries and
(B, Hkv, T, D) keys/values with Hq % Hkv == 0, expands KV heads, flattens to
(B·Hq, T, D), and dispatches to the Pallas kernel (interpret on CPU) or the
dense oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.local_attention.kernel import local_attention_pallas
from repro.kernels.local_attention.ref import local_attention_ref
from repro.kernels.ops_registry import resolve_interpret


def local_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    window: int,
    *,
    softcap: float = 0.0,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not divisible by Hkv={Hkv}")
    rep = Hq // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(B * Hq, T, D)
    kf = k.reshape(B * Hq, T, D)
    vf = v.reshape(B * Hq, T, D)
    if use_kernel:
        o = local_attention_pallas(
            qf, kf, vf, window=window, softcap=softcap,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
    else:
        o = local_attention_ref(qf, kf, vf, window=window, softcap=softcap)
    return o.reshape(B, Hq, T, D)
