"""Plain per-element oracles for the keyed window store.

Independent of the engines they check: a dict of per-key Python lists, one
``monoid.lift`` per row and a left-to-right ``monoid.combine`` fold per
output (older operand LEFT), with no sorting, directory, carry lanes or
kernels.  The tests and ``chip_smoke.py`` hold
:class:`repro.core.keyed.KeyedChunkedStream` and the analytics service to
these bit-exactly.  Each output costs O(window) combines: replay short
streams, or only the rows a check needs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["per_key_reference", "per_key_horizon_reference"]


def per_key_reference(monoid, keys, vals, window):
    """Dict of per-key element lists; output = left-to-right fold of each
    key's last min(window, seen) lifted elements."""
    hist: dict = {}
    outs = []
    for k, v in zip(keys, vals):
        h = hist.setdefault(int(k), [])
        h.append(monoid.lift(v))
        if len(h) > window:
            h.pop(0)
        acc = h[0]
        for e in h[1:]:
            acc = monoid.combine(acc, e)
        outs.append(acc)
    return jax.tree.map(lambda *rows: jnp.stack(rows), *outs)


def per_key_horizon_reference(monoid, keys, vals, ts, window, horizon):
    """Timestamped dict oracle for ``horizon=`` mode: each key keeps its
    last min(window, seen) (ts, lifted) pairs; output at row j folds — older
    operand LEFT — only the retained pairs with ``ts' > ts[j] - horizon``."""
    hist: dict = {}
    outs = []
    for k, v, t in zip(keys, vals, np.asarray(ts, np.float32)):
        h = hist.setdefault(int(k), [])
        h.append((float(t), monoid.lift(v)))
        if len(h) > window:
            h.pop(0)
        acc = monoid.identity()
        for tt, e in h:
            if tt > float(t) - horizon:
                acc = monoid.combine(acc, e)
        outs.append(acc)
    return jax.tree.map(lambda *rows: jnp.stack(rows), *outs)
