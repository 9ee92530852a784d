"""Public op: dense sliding-window aggregation with kernel/oracle dispatch.

On TPU this routes to the Pallas VHGW kernel (3 combines/element, bandwidth
bound).  On CPU (this container) the kernel runs in ``interpret=True`` mode —
the same kernel body, executed in Python, used by tests to validate the TPU
tiling logic.  ``sliding_window_agg`` also accepts >2-D inputs by flattening
leading axes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ops_registry import resolve_interpret
from repro.kernels.sliding_window.kernel import sliding_window_pallas
from repro.kernels.sliding_window.ref import sliding_window_ref


def sliding_window_agg(
    x: jax.Array,
    window: int,
    op: str = "sum",
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_b: int = 8,
) -> jax.Array:
    """``y[..., t] = x[..., t-w+1] ⊗ … ⊗ x[..., t]`` along the last axis."""
    interpret = resolve_interpret(interpret)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    if use_kernel:
        y = sliding_window_pallas(
            x2, window=window, op=op, block_b=block_b, interpret=interpret
        )
    else:
        y = sliding_window_ref(x2, window=window, op=op)
    return y.reshape(lead + (x.shape[-1],))
