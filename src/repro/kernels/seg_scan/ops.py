"""Public ops: batched segmented suffix/prefix scans, kernel/oracle dispatch."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ops_registry import resolve_interpret
from repro.kernels.seg_scan.kernel import (
    seg_prefix_scan_pallas,
    seg_suffix_scan_pallas,
)
from repro.kernels.seg_scan.ref import seg_prefix_scan_ref, seg_suffix_scan_ref


def seg_suffix_scan_op(
    x: jax.Array,
    flags: jax.Array,
    op: str = "sum",
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_b: int = 8,
    block_t: int = 256,
) -> jax.Array:
    """Segmented suffix scan along the last axis: ``y[..., t] = x[..., t] ⊗
    … ⊗ x[..., e(t)]`` with ``flags`` marking segment ends.  This is the
    scalar-monoid fast path of
    :meth:`repro.core.keyed.KeyedWindowStore.update_chunk` (selected there
    through the ``ops_registry.op_for_monoid`` structural gate)."""
    x = jnp.asarray(x)
    flags = jnp.asarray(flags)
    if flags.shape != x.shape:
        flags = jnp.broadcast_to(flags, x.shape)
    interpret = resolve_interpret(interpret)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    f2 = flags.reshape((-1, x.shape[-1]))
    if use_kernel:
        y = seg_suffix_scan_pallas(
            x2, f2, op=op, block_b=block_b, block_t=block_t,
            interpret=interpret,
        )
    else:
        y = seg_suffix_scan_ref(x2, flags=f2, op=op)
    return y.reshape(lead + (x.shape[-1],))


def seg_prefix_scan_op(
    x: jax.Array,
    flags: jax.Array,
    op: str = "sum",
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_b: int = 8,
    block_t: int = 256,
) -> jax.Array:
    """Segmented prefix scan along the last axis: ``y[..., t] = x[..., s(t)]
    ⊗ … ⊗ x[..., t]`` with ``flags`` marking segment STARTS — the second
    half of the keyed flip sweep (same ``op_for_monoid`` gate as
    :func:`seg_suffix_scan_op`)."""
    x = jnp.asarray(x)
    flags = jnp.asarray(flags)
    if flags.shape != x.shape:
        flags = jnp.broadcast_to(flags, x.shape)
    interpret = resolve_interpret(interpret)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    f2 = flags.reshape((-1, x.shape[-1]))
    if use_kernel:
        y = seg_prefix_scan_pallas(
            x2, f2, op=op, block_b=block_b, block_t=block_t,
            interpret=interpret,
        )
    else:
        y = seg_prefix_scan_ref(x2, flags=f2, op=op)
    return y.reshape(lead + (x.shape[-1],))
