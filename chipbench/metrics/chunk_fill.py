"""``chunk_fill``: rows drained over the rows the drained chunks could
hold, in the window, in percent (``stats()`` counters ``drained_rows`` and
``chunks`` read at the window's two ends)."""


def read(rec):
    a, b = rec["stats_open"], rec["stats_close"]
    chunks = b["chunks"] - a["chunks"]
    if chunks <= 0:
        return None
    return 100.0 * (b["drained_rows"] - a["drained_rows"]) / (
        chunks * rec["chunk"])
