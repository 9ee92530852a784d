"""``query_p50_ms``: median time from when a dashboard ``GET /query`` was
due to its reply, over every successful query due in the window."""

from chipbench import window


def read(rec):
    q = rec["queries"]
    sel = window.due_in_window(q, rec) & (q[:, 4] == 200)
    return window.percentile((q[sel, 3] - q[sel, 1]) * 1e3, 50)
