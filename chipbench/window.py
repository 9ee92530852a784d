"""Shared arithmetic of the window's per-request times (not a metric)."""

import numpy as np


def due_in_window(rows, rec):
    return (rows[:, 1] >= rec["t_open"]) & (rows[:, 1] < rec["t_close"])


def visible_ms(rec):
    """Due → queryable, ms, for every acknowledged batch due in the
    window."""
    b = rec["batches"]
    sel = due_in_window(b, rec) & (b[:, 4] == 200)
    return (rec["visible"][sel] - b[sel, 1]) * 1e3


def percentile(x, q):
    x = np.asarray(x, np.float64)
    if x.size == 0 or np.isnan(x).any():
        return None
    return float(np.percentile(x, q))


def visible_times(times, counts, tenants, seqs) -> np.ndarray:
    """The first poll time at which tenant ``tenants[j]``'s queryable
    count reached ``seqs[j]`` (NaN if it never did).  Counts only grow."""
    out = np.full(len(seqs), np.nan)
    for j, (i, s) in enumerate(zip(tenants, seqs)):
        k = int(np.searchsorted(counts[:, i], s, "left")) if len(times) else 0
        if s > 0 and k < len(times):
            out[j] = times[k]
    return out
