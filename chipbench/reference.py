"""Plain per-key reference for the service's windowed folds.

Semantics (the keyed store's ``horizon=`` mode): a key's answer is the
fold, older operand left, of its last ``min(window, seen)`` rows whose
event time is later than ``t_last - horizon``, where ``t_last`` is the
time of the key's last row.  A key never seen answers ``found = False``
with the fold's identity, as wide as a row.  Each running fold is
rounded to the fold's stated type (``wrap``), or, for the precision
control, to the type below it (``narrow``).

The fold is ``chipbench/folds/<monoid>.py``, found by the configuration's
``service.monoid`` (:func:`chipbench.byname.fold`): ``IDENTITY``,
``combine(acc, row_value)``, ``wrap`` and ``narrow``.  Imports nothing of
the program: a stable sort and a left-to-right Python loop.
"""

from __future__ import annotations

import numpy as np


def key_folds(fold, keys, ts, xs, queried, *, window: int, horizon: float,
              narrow: bool = False) -> dict:
    """``{key: (found, fold)}`` for each key in ``queried``, over one
    tenant's rows ``keys``/``ts``/``xs`` in arrival order; ``xs`` is (n,)
    or (n, k)."""
    rounded = fold.narrow if narrow else fold.wrap
    keys, xs = np.asarray(keys), np.asarray(xs)
    ident = (fold.IDENTITY if xs.ndim == 1
             else np.full(xs.shape[1:], fold.IDENTITY))
    order = np.argsort(keys, kind="stable")  # arrival order within a key
    sk = keys[order]
    out = {}
    for k in queried:
        k = int(k)
        lo = int(np.searchsorted(sk, k, "left"))
        hi = int(np.searchsorted(sk, k, "right"))
        if lo == hi:
            out[k] = (False, ident)
            continue
        rows = order[max(lo, hi - window):hi]
        t_last = float(ts[rows[-1]])
        acc = fold.IDENTITY
        for r in rows:
            if float(ts[r]) > t_last - horizon:
                acc = rounded(fold.combine(acc, xs[r]))
        out[k] = (True, acc)
    return out
