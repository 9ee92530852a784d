"""``drain_ms``: the window's length over the chunks drained in it
(``stats()`` counter ``chunks``), in ms."""


def read(rec):
    chunks = rec["stats_close"]["chunks"] - rec["stats_open"]["chunks"]
    if chunks <= 0:
        return None
    return rec["seconds"] * 1e3 / chunks
