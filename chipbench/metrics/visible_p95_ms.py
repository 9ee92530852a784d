"""``visible_p95_ms``: the 95th percentile of due → queryable over every
acknowledged batch due in the window (see ``visible_p50_ms``)."""

from chipbench import window


def read(rec):
    return window.percentile(window.visible_ms(rec), 95)
