"""``post_p95_ms``: 95th percentile of due → 200 reply of ``POST /ingest``
in the window (client clock): the HTTP front end's share of latency."""

from chipbench import window


def read(rec):
    b = rec["batches"]
    sel = window.due_in_window(b, rec) & (b[:, 4] == 200)
    return window.percentile((b[sel, 3] - b[sel, 1]) * 1e3, 95)
