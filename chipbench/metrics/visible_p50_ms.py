"""``visible_p50_ms``: median time from when a ``POST /ingest`` batch was
due to when its rows were queryable, over every acknowledged batch due in
the window (host clock, client and server on one monotonic clock)."""

from chipbench import window


def read(rec):
    return window.percentile(window.visible_ms(rec), 50)
