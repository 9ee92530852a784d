"""Driver ``saturate``: keep every tenant's backlog inside a band.

One sender thread.  Every ``stats_every_s`` it reads ``GET /stats`` and
takes each tenant's backlog (accepted but not yet queryable rows); between
reads it counts what it posted itself.  A tenant whose backlog is below
``backlog_chunks[0]`` chunks gets ``batch_rows``-row batches, round-robin
with the others, until it reaches ``backlog_chunks[1]`` chunks.  So drained
chunks are full, and the rate is what the service drains: a faster
program is never outrun by this client.

Mix parameters: ``batch_rows``, ``backlog_chunks`` ([low, high]),
``stats_every_s``; the job gives the chunk size and each tenant's first
window row (``start_row``).
"""

from __future__ import annotations

import time

from chipbench import client


def prepare(job: dict, streams: list) -> dict:
    return {"job": job, "streams": streams}


def run(plan: dict, go: dict) -> dict:
    job, streams = plan["job"], plan["streams"]
    mix, names = job["mix"], job["tenants"]
    batch, chunk = mix["batch_rows"], job["chunk"]
    low, high = (c * chunk for c in mix["backlog_chunks"])
    url, t_open, t_close = go["url"], go["t_open"], go["t_close"]
    nxt = [job["start_row"]] * len(names)
    backlog = [0] * len(names)
    batches = []  # [tenant, due, sent, replied, status, seq, rows, row_lo]
    stats_reads = 0
    client.sleep_until(t_open)
    t_stats = 0.0
    while True:
        now = time.monotonic()
        if now >= t_close:
            break
        if now - t_stats >= mix["stats_every_s"]:
            status, doc = client.request(url, "GET", "/stats")
            t_stats = now
            if status == 200:
                stats_reads += 1
                per = doc["per_tenant"]
                backlog = [per[n]["pending_rows"] if n in per else 0
                           for n in names]
        want = [i for i in range(len(names)) if backlog[i] < low]
        if not want:
            time.sleep(min(0.002, max(0.0, t_close - now)))
            continue
        # round-robin: one batch per needy tenant per pass, up to `high`
        while want and time.monotonic() < t_close:
            for i in list(want):
                lo = nxt[i]
                due = time.monotonic()
                body = client.ingest_body(names[i],
                                          streams[i].rows(lo, lo + batch))
                sent = time.monotonic()
                status, doc = client.request(url, "POST", "/ingest", body)
                replied = time.monotonic()
                seq = doc.get("seq", -1) if status == 200 else -1
                batches.append([i, due, sent, replied, status, seq, batch,
                                lo])
                if status == 200:
                    nxt[i] += batch
                    backlog[i] += batch
                if backlog[i] >= high or status != 200:
                    want.remove(i)
    return {"batches": batches, "queries": [],
            "lateness_s": [], "stats_reads": stats_reads}
