"""Public op: batched suffix scan with kernel/oracle dispatch."""

from __future__ import annotations

import jax

from repro.kernels.ops_registry import resolve_interpret
from repro.kernels.suffix_scan.kernel import suffix_scan_pallas
from repro.kernels.suffix_scan.ref import suffix_scan_ref


def suffix_scan(
    x: jax.Array,
    op: str = "sum",
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_b: int = 8,
    block_t: int = 256,
) -> jax.Array:
    """``y[..., t] = x[..., t] ⊗ … ⊗ x[..., T-1]`` along the last axis."""
    interpret = resolve_interpret(interpret)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    if use_kernel:
        y = suffix_scan_pallas(
            x2, op=op, block_b=block_b, block_t=block_t, interpret=interpret
        )
    else:
        y = suffix_scan_ref(x2, op=op)
    return y.reshape(lead + (x.shape[-1],))
