"""Driver ``open_loop``: batches and dashboard queries on a fixed schedule.

Batches of ``batch_rows`` rows arrive at ``rate_events_per_s`` in all,
the same number for every tenant; queries (``GET /query?tenant=``, the
dashboard form) arrive at ``queries_per_s``, round-robin over tenants.
Inter-arrival gaps are the exponential distribution's mid-quantiles
(:func:`chipbench.gen.exponential_gaps`) in one fixed order that each seed
rotates, with the tenant of each batch: every seed offers the same load,
bursts included, starting at another point of it.

Open loop: the schedule never waits for the service.  Each tenant has one
sender thread, because a tenant's batches must arrive in order (their
event times are non-decreasing); ``query_threads`` threads share the
queries.  A request is timed from when it was due, so a sender that falls
behind charges its wait to the service; how late each request went out is
returned as ``lateness_s``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from chipbench import client, gen


def prepare(job: dict, streams: list) -> dict:
    mix, names, seed = job["mix"], job["tenants"], job["seed"]
    seconds, batch = job["seconds"], mix["batch_rows"]
    n = int(round(mix["rate_events_per_s"] * seconds / batch))
    shift = gen.rotation(n, seed, 11)
    due = np.cumsum(np.roll(gen.exponential_gaps(n, seconds / n, 11), shift))
    tenant = np.roll(gen.balanced_order(n, len(names), 12), shift)
    per = [[] for _ in names]
    nxt = [job["start_row"]] * len(names)
    for d, i in zip(due.tolist(), tenant.tolist()):
        lo = nxt[i]
        nxt[i] += batch
        body = client.ingest_body(names[i], streams[i].rows(lo, lo + batch))
        per[i].append((d, lo, body))
    nq = int(round(mix["queries_per_s"] * seconds))
    qdue = np.cumsum(np.roll(gen.exponential_gaps(nq, seconds / nq, 13),
                             gen.rotation(nq, seed, 13))) \
        if nq else np.zeros(0)
    queries = [(d, j % len(names)) for j, d in enumerate(qdue.tolist())]
    return {"job": job, "per_tenant": per, "queries": queries}


def run(plan: dict, go: dict) -> dict:
    job, mix = plan["job"], plan["job"]["mix"]
    names, batch = job["tenants"], mix["batch_rows"]
    url, t_open = go["url"], go["t_open"]
    batches, queries, lateness = [], [], []
    lock = threading.Lock()

    def send_batches(i):
        for d, lo, body in plan["per_tenant"][i]:
            due = t_open + d
            client.sleep_until(due)
            sent = time.monotonic()
            status, doc = client.request(url, "POST", "/ingest", body)
            replied = time.monotonic()
            seq = doc.get("seq", -1) if status == 200 else -1
            with lock:
                batches.append([i, due, sent, replied, status, seq, batch,
                                lo])
                lateness.append(sent - due)

    qnext = [0]

    def send_queries():
        while True:
            with lock:
                j = qnext[0]
                qnext[0] += 1
            if j >= len(plan["queries"]):
                return
            d, i = plan["queries"][j]
            due = t_open + d
            client.sleep_until(due)
            sent = time.monotonic()
            status, _ = client.request(url, "GET", f"/query?tenant={names[i]}")
            replied = time.monotonic()
            with lock:
                queries.append([i, due, sent, replied, status])
                lateness.append(sent - due)

    threads = [threading.Thread(target=send_batches, args=(i,))
               for i in range(len(names))]
    threads += [threading.Thread(target=send_queries)
                for _ in range(mix["query_threads"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    batches.sort(key=lambda b: b[1])
    queries.sort(key=lambda q: q[1])
    return {"batches": batches, "queries": queries, "lateness_s": lateness,
            "stats_reads": 0}
