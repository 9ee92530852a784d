"""Plain per-key reference for the service's windowed folds.

Semantics (the keyed store's ``horizon=`` mode): a key's answer is the
fold, older operand left, of its last ``min(window, seen)`` rows whose
event time is later than ``t_last - horizon``, where ``t_last`` is the
time of the key's last row.  A key never seen answers ``found = False``
with the monoid's identity.  Integer folds wrap at ``bits`` bits, as the
stated int32 does at 32.

Imports nothing of the program: a dict of monoids, a stable sort and a
left-to-right Python loop.
"""

from __future__ import annotations

import numpy as np

MONOIDS = {
    # name: (identity at `bits` bits, combine)
    "sum_i32": (lambda bits: 0, lambda a, b: a + b),
    "max_i32": (lambda bits: -(1 << (bits - 1)), max),
}


def _wrap(v: int, bits: int) -> int:
    half = 1 << (bits - 1)
    return (v + half) % (1 << bits) - half


def key_folds(monoid: str, keys, ts, xs, queried, *, window: int,
              horizon: float, bits: int = 32) -> dict:
    """``{key: (found, fold)}`` for each key in ``queried``, over one
    tenant's rows ``keys``/``ts``/``xs`` in arrival order."""
    ident_fn, combine = MONOIDS[monoid]
    ident = ident_fn(bits)
    keys = np.asarray(keys)
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
        acc = ident
        for r in rows:
            if float(ts[r]) > t_last - horizon:
                acc = _wrap(combine(acc, int(xs[r])), bits)
        out[k] = (True, acc)
    return out
