"""Shared elementwise-monoid op registry for all Pallas kernels.

Every kernel (sliding_window, suffix_scan, ...) and the chunked streaming
engine (:mod:`repro.core.chunked`) dispatch through this single table, so a
new elementwise monoid is added in ONE place and becomes available to every
bulk code path at once.

An *op* here is a scalar (elementwise) associative combine with a constant
identity — the subset of :mod:`repro.core.monoids` that maps 1:1 onto VPU
lanes.  Pytree-valued monoids (mean, m4, affine, ...) cannot use the scalar
kernels; they go through the generic ``associative_scan`` path of the
chunked engine instead.  :func:`op_for_monoid` is the structural gate: the
keyed flip sweep routes BOTH halves (``seg_scan``'s segmented suffix and
prefix kernels) through it, falling back to the lax pair-operator scans for
pytree aggregates.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

# Large-magnitude stand-ins for ±inf: Pallas TPU kernels prefer finite
# identities (inf arithmetic is dtype-fragile on VPU), and the values are
# far outside any realistic data range.
_NEG_BIG = {
    jnp.dtype(jnp.float32): -3.0e38,
    jnp.dtype(jnp.bfloat16): -3.0e38,
    jnp.dtype(jnp.float16): -6.0e4,
}


def _lse(a, b):
    m = jnp.maximum(a, b)
    lo = jnp.minimum(a, b)
    # stable: m + log1p(exp(lo - m)); exp(-inf-ish) underflows to 0.
    return m + jnp.log1p(jnp.exp(lo - m))


_COMBINE: dict[str, Callable[[jax.Array, jax.Array], jax.Array]] = {
    "sum": lambda a, b: a + b,
    "prod": lambda a, b: a * b,
    "max": jnp.maximum,
    "min": jnp.minimum,
    "logsumexp": _lse,
}


def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode by default: everywhere
    but a TPU backend (CPU tests execute the same kernel bodies in Python).
    The ONE place this choice is made; the raw ``*_pallas`` kernels take
    ``interpret`` explicitly."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` flag, or :func:`default_interpret`."""
    return default_interpret() if interpret is None else bool(interpret)


def available_ops() -> list[str]:
    """Names of the elementwise ops every kernel supports."""
    return sorted(_COMBINE)


def combine_fn(op: str) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """The associative combine for ``op`` (older operand LEFT)."""
    try:
        return _COMBINE[op]
    except KeyError:
        raise ValueError(f"unsupported op {op!r}; have {available_ops()}") from None


def identity_for(op: str, dtype) -> float | int:
    """The identity element of ``op`` as a scalar fill value for ``dtype``."""
    dtype = jnp.dtype(dtype)
    if op == "sum":
        return 0
    if op == "prod":
        return 1
    if op == "max":
        return _NEG_BIG.get(dtype, jnp.iinfo(dtype).min if dtype.kind == "i" else -3.0e38)
    if op == "logsumexp":
        return _NEG_BIG.get(dtype, -3.0e38)
    if op == "min":
        if dtype.kind == "i":
            return jnp.iinfo(dtype).max
        return -_NEG_BIG.get(dtype, -3.0e38)
    raise ValueError(f"unsupported op {op!r}; have {available_ops()}")


# Monoid-registry names (repro.core.monoids) whose combine is bit-identical
# to a kernel op on a plain scalar Agg.  Used to auto-route ChunkedStream.
_MONOID_NAME_TO_OP = {
    "sum": "sum",
    "count": "sum",
    "max": "max",
    "min": "min",
    "logsumexp": "logsumexp",
}



def op_for_monoid(monoid) -> Optional[str]:
    """Kernel op equivalent to ``monoid``, or None if it needs the generic path.

    Matching is by the monoid's registered name prefix (``sum_float32`` →
    ``sum``), gated on the Agg actually being a single scalar leaf — pytree
    aggregates (sketches like KLL/Bloom, mean pairs, m4, affine maps,
    product monoids) always take the generic path even if a caller aliases
    one to a kernel-op name.
    """
    base = monoid.name.split("_")[0].split("#")[0]
    op = _MONOID_NAME_TO_OP.get(base)
    if op is None:
        return None
    leaves = jax.tree.leaves(monoid.identity())
    if len(leaves) != 1 or jnp.ndim(leaves[0]) != 0:
        return None
    return op
