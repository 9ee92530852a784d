"""Arrival patterns shared by the traffic drivers, whatever the data.

Numpy only: the traffic client imports it without JAX.  A deployment's
rows come from its generator, ``chipbench/generators/<name>.py``, found
by the ``name`` in the configuration's ``generator`` section
(:func:`chipbench.byname.tenant_streams`).
"""

from __future__ import annotations

import numpy as np

# Every seed offers the same arrival pattern, rotated: which gaps come
# together decides how deep the queues get, so a pattern drawn anew per
# seed would make the tail latency a property of the seed.
PATTERN_SEED = 20090928


def exponential_gaps(n: int, mean: float, salt: int) -> np.ndarray:
    """``n`` inter-arrival gaps of mean ``mean``: the exponential
    distribution's ``n`` mid-quantiles in one fixed shuffled order."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) * mean
    return np.random.default_rng((PATTERN_SEED, salt)).permutation(gaps)


def balanced_order(n: int, groups: int, salt: int) -> np.ndarray:
    """``n`` group ids, as equal in count as ``n`` allows, in one fixed
    shuffled order."""
    ids = np.arange(n) % groups
    return np.random.default_rng((PATTERN_SEED, salt)).permutation(ids)


def rotation(n: int, seed, salt: int) -> int:
    """The seed's rotation of a fixed pattern of length ``n``."""
    return int(np.random.default_rng((int(seed), salt)).integers(max(n, 1)))
