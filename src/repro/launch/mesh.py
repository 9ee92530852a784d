"""Production mesh definitions.

TPU v5e pod of 256 chips as a (data=16, model=16) mesh; the multi-pod
configuration stacks 2 pods into (pod=2, data=16, model=16) = 512 chips.
Data parallelism spans ("pod", "data"); tensor/expert parallelism stays
inside a pod on "model" (ICI-local).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialization (see launch/dryrun.py lines 1–2).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link


def make_mesh(shape, axes):
    """A mesh whose axes the compiler shards across (``AxisType.Auto``).

    The train step leaves intermediate shardings to propagation from the
    parameters' specs; ``jax.make_mesh``'s default explicit axes would
    instead require an output sharding on every gather and contraction
    over a sharded dimension."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def chips(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
