"""Public op: fused chunked GLA with kernel/oracle dispatch.

On TPU this is the drop-in fast path for the rwkv6/zamba2 recurrence — the
HBM round-trips of the unfused chunk chain (the §Perf cell-3 memory-term
bound) collapse into one VMEM-resident body with the state carried in
scratch.  On CPU it runs in interpret mode for validation.
"""

from __future__ import annotations

import jax

from repro.kernels.gla.kernel import gla_pallas
from repro.kernels.gla.ref import gla_ref
from repro.kernels.ops_registry import resolve_interpret


def gla(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    a: jax.Array,
    bonus_u: jax.Array | None = None,
    *,
    chunk: int = 64,
    variant: str = "mamba",
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    if use_kernel:
        return gla_pallas(
            r, k, v, a, bonus_u, chunk=chunk, variant=variant,
            interpret=interpret,
        )
    return gla_ref(r, k, v, a, bonus_u, variant=variant)
