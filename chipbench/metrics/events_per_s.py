"""``events_per_s``: rows of acknowledged batches that became queryable
inside the window, over the window's length (host clock; a batch is
queryable at the first watcher poll whose count reaches its ``seq``)."""


def read(rec):
    b, vis = rec["batches"], rec["visible"]
    inside = (vis > rec["t_open"]) & (vis <= rec["t_close"])
    return float(b[inside, 6].sum()) / rec["seconds"]
