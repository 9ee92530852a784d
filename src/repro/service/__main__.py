"""Run the analytics service from the command line::

    PYTHONPATH=src python -m repro.service --port 8080 --window 256 \
        --horizon 64 --quota-rows-per-s 100000

Serves until interrupted; ``--obs`` attaches the metrics registry so
``GET /metrics`` exposes per-tenant series.
"""

from __future__ import annotations

import argparse
import time

from repro.compile_cache import enable_compile_cache
from repro.service.config import ServiceConfig
from repro.service.core import AnalyticsService
from repro.service.http import ServiceHTTPServer


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="repro.service",
                                description=__doc__.splitlines()[0])
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="0 = ephemeral (printed at startup)")
    p.add_argument("--monoid", default="sum_i32")
    p.add_argument("--window", type=int, default=256)
    p.add_argument("--horizon", type=float, default=64.0,
                   help="event-time span; <= 0 for count windows")
    p.add_argument("--slots", type=int, default=8192)
    p.add_argument("--chunk", type=int, default=1024)
    p.add_argument("--max-batch", type=int, default=512)
    p.add_argument("--quota-rows-per-s", type=float, default=100_000.0)
    p.add_argument("--quota-burst", type=float, default=20_000.0)
    p.add_argument("--no-rollup", action="store_true")
    p.add_argument("--obs", action="store_true",
                   help="attach the metrics registry (GET /metrics)")
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = ServiceConfig(
        monoid=args.monoid,
        window=args.window,
        horizon=args.horizon if args.horizon > 0 else None,
        slots=args.slots,
        chunk=args.chunk,
        max_batch=args.max_batch,
        quota_rows_per_s=args.quota_rows_per_s,
        quota_burst=args.quota_burst,
        rollup=not args.no_rollup,
    )
    svc = AnalyticsService(cfg)
    if args.obs:
        svc.attach_obs()
    with ServiceHTTPServer(svc, host=args.host, port=args.port) as srv:
        print(f"serving on {srv.url}  (POST /ingest, GET /query,"
              f" /stats, /healthz{', /metrics' if args.obs else ''})",
              flush=True)
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
