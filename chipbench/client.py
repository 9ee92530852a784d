#!/usr/bin/env python3
"""The traffic client: a process of its own that posts over real HTTP.

Started by ``run.py``; never imports JAX.  Protocol on its standard
streams, one JSON document per line:

1. in: the job (``root``, ``seed``, ``generator``, ``mix``, ``tenants``);
   the client builds the streams with the generator the configuration
   names and calls the mix's driver's
   ``prepare(job, streams)``, then writes ``{"ready": true}``;
2. in: ``{"url", "t_open", "t_close"}`` (``time.monotonic`` seconds, the
   clock the server process reads too); the client calls the traffic driver's
   ``run(plan, go)`` and writes its records as the last line.

A driver is ``chipbench/traffic/<mix["driver"]>.py`` and a generator
``chipbench/generators/<generator["name"]>.py``, each found by name
(:mod:`chipbench.byname`).
"""

from __future__ import annotations

import http.client
import json
import pathlib
import sys
import time
from urllib.parse import urlparse


def request(url: str, method: str, path: str, body: bytes = None,
            timeout: float = 120.0) -> tuple:
    """One HTTP request → ``(status, parsed JSON reply or None)``; a
    connection failure is status 0."""
    u = urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    try:
        headers = {"Content-Type": "application/json"} if body else {}
        conn.request(method, path, body=body, headers=headers)
        r = conn.getresponse()
        data = r.read()
        try:
            return r.status, json.loads(data)
        except ValueError:
            return r.status, None
    except OSError:
        return 0, None
    finally:
        conn.close()


def ingest_body(tenant: str, rows: tuple) -> bytes:
    k, t, x = rows
    return json.dumps({"tenant": tenant, "keys": k.tolist(),
                       "ts": t.tolist(), "values": x.tolist()}).encode()


def sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def main() -> int:
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))

    from chipbench import byname

    job = json.loads(sys.stdin.readline())
    driver = byname.driver(job["root"], job["mix"]["driver"])
    streams = byname.tenant_streams(job["root"], job["seed"], job["generator"])
    plan = driver.prepare(job, streams)
    print(json.dumps({"ready": True}), flush=True)
    line = sys.stdin.readline()
    if not line:
        return 1
    out = driver.run(plan, json.loads(line))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
