"""Benchmark driver — one function per paper table/figure.

Prints ``name,...,derived`` CSV lines AND writes a machine-readable
``BENCH_<name>.json`` per benchmark (parsed rows + backend + timestamp) so
the perf trajectory is comparable across PRs (``--out-dir`` to redirect,
``--no-json`` to disable).  Scales are reduced for the single-core CPU
container (see benchmarks/common.py); EXPERIMENTS.md records a full run's
output.

  Fig 9  → bench_latency      per-op latency + exact ⊗-count distributions
  Fig 10 → bench_throughput   throughput vs window size (static)
  Fig 11 → bench_dynamic      fill-and-drain dynamic windows
  Fig 12 → bench_eventtime    event-time windows, bursty stream
  §2.1   → bench_batched      SIMD/vmap batched SWAG (beyond paper)
  §8.2   → bench_chunked      chunked bulk engine vs per-element stream
  beyond → bench_keyed        keyed window store: K per-key windows, bulk
  §Roofline → roofline_table  rendered from experiments/dryrun/*.json
"""

import argparse
import datetime
import json
import pathlib
import sys


def parse_rows(rows) -> list:
    """CSV benchmark rows → dicts: ``k=v`` fields typed as floats where
    possible, bare fields collected under ``labels``."""
    parsed = []
    for row in rows or []:
        rec = {"raw": str(row), "labels": []}
        for part in str(row).split(","):
            if "=" in part:
                k, v = part.split("=", 1)
                try:
                    rec[k] = float(v)
                except ValueError:
                    rec[k] = v
            else:
                rec["labels"].append(part)
        parsed.append(rec)
    return parsed


def emit_json(name: str, rows, out_dir: str = ".") -> pathlib.Path:
    """Write ``BENCH_<name>.json``: parsed rows + backend, so the perf
    trajectory (items/s per window/T/engine) is tracked across PRs."""
    import jax

    payload = {
        "bench": name,
        "backend": jax.default_backend(),
        "generated": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "rows": parse_rows(rows),
    }
    path = pathlib.Path(out_dir) / f"BENCH_{name}.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {path}")
    return path


# rows with these labels are informational, not regression-gated: the
# per-key Python loop and the Fig-12 relvar rows time host Python-loop
# dispatch overhead (noisy across machines), speedup/tune rows carry no
# items_per_s of their own, and bulk_horizon is the first keyed event-time
# baseline (no committed history to gate against yet)
_COMPARE_SKIP_LABELS = {"per_key_loop", "relvar", "speedup", "tune",
                        "tune_best", "bulk_horizon"}


def _row_key(rec: dict):
    """Identity of a benchmark row for --compare matching: its bare labels
    plus every ``k=v`` parameter EXCEPT the measured outputs."""
    drop = {"raw", "labels", "items_per_s", "x", "roofline_frac"}
    params = tuple(sorted(
        (k, v) for k, v in rec.items() if k not in drop
    ))
    return (tuple(rec.get("labels", ())), params)


def compare_rows(current_rows, baseline_path: str, threshold: float) -> int:
    """Diff current rows against a committed BENCH JSON: rows are matched
    by labels+parameters and FAIL when ``items_per_s`` falls below
    ``threshold ×`` the baseline.  Returns the number of failures (and
    counts zero matched rows as a failure — a silently-empty gate guards
    nothing)."""
    with open(baseline_path) as f:
        baseline = json.load(f)
    base = {}
    for rec in baseline.get("rows", []):
        if "items_per_s" not in rec:
            continue
        if _COMPARE_SKIP_LABELS & set(rec.get("labels", ())):
            continue
        base[_row_key(rec)] = rec["items_per_s"]
    matched = failures = 0
    for rec in parse_rows(current_rows):
        key = _row_key(rec)
        if key not in base or "items_per_s" not in rec:
            continue
        matched += 1
        ratio = rec["items_per_s"] / base[key] if base[key] > 0 else 1.0
        status = "OK" if ratio >= threshold else "REGRESSION"
        if ratio < threshold:
            failures += 1
        print(
            f"# compare {status}: {rec['raw']}  "
            f"baseline={base[key]:.0f} ratio={ratio:.2f} "
            f"(threshold {threshold})"
        )
    if matched == 0:
        print(f"# compare FAILED: no rows matched {baseline_path}")
        return 1
    print(f"# compare: {matched} rows matched, {failures} regressions")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: latency,throughput,dynamic,eventtime,"
                         "batched,chunked,keyed,service,roofline")
    ap.add_argument("--quick", action="store_true", help="reduced sizes")
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_<name>.json summaries")
    ap.add_argument("--no-json", action="store_true",
                    help="skip the JSON summaries")
    ap.add_argument("--tune", action="store_true",
                    help="autotune mode: sweep chunk size per (backend, K, "
                         "window) for the keyed store and emit the best "
                         "configuration (writes BENCH_keyed_tune.json)")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="diff this run's rows against a committed BENCH "
                         "JSON (matched by labels+params) and exit non-zero "
                         "on items/s regressions; per_key_loop rows are "
                         "informational and never gated")
    ap.add_argument("--threshold", type=float, default=0.8,
                    help="minimum current/baseline items_per_s ratio for "
                         "--compare (default 0.8)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    want = set(args.only.split(",")) if args.only else None
    all_rows: list = []

    def on(name):
        return want is None or name in want

    def done(name, rows):
        all_rows.extend(rows or [])
        if not args.no_json:
            # --quick runs smaller configurations: write (and stamp) them as
            # BENCH_<name>_quick.json so a quick run can never clobber the
            # committed full-scale baselines
            emit_json(name + ("_quick" if args.quick else ""), rows,
                      args.out_dir)

    if args.tune:
        from benchmarks import bench_keyed

        print("# autotune — keyed chunk sweep")
        if args.quick:
            rows = bench_keyed.tune(Ks=(256, 65536),
                                    chunks=(512, 1024, 4096), T=16384)
        else:
            rows = bench_keyed.tune()
        done("keyed_tune", rows)
        if args.compare:
            sys.exit(1 if compare_rows(rows, args.compare, args.threshold)
                     else 0)
        return

    from benchmarks import (
        bench_batched,
        bench_chunked,
        bench_dynamic,
        bench_eventtime,
        bench_keyed,
        bench_latency,
        bench_throughput,
        roofline_table,
    )

    if on("latency"):
        print("# Fig 9 — latency")
        if args.quick:
            rows = bench_latency.main(window=2**8, rounds=800, operators=("sum",))
        else:
            rows = bench_latency.main()
        done("latency", rows)
    if on("throughput"):
        print("# Fig 10 — throughput (static windows)")
        if args.quick:
            rows = bench_throughput.main(windows=(2**4,), items=50_000,
                                         operators=("sum",))
        else:
            rows = bench_throughput.main()
        done("throughput", rows)
    if on("dynamic"):
        print("# Fig 11 — throughput (dynamic fill-and-drain)")
        if args.quick:
            rows = bench_dynamic.main(windows=(2**4,), items=30_000,
                                      operators=("sum",))
        else:
            rows = bench_dynamic.main()
        done("dynamic", rows)
    if on("eventtime"):
        print("# Fig 12 — event-time windows (synthetic bursty stream)")
        if args.quick:
            # bulk rows keep horizon=1024 so CI gates the constant-combine
            # flip-sweep regime, not just the small-window one
            rows = bench_eventtime.main(n_items=2000, horizons=(256, 1024),
                                        bulk_T=12000)
        else:
            rows = bench_eventtime.main()
        done("eventtime", rows)
    if on("batched"):
        print("# beyond-paper — batched/SIMD SWAG")
        if args.quick:
            rows = bench_batched.main(batches=(16,), steps=4000)
        else:
            rows = bench_batched.main()
        done("batched", rows)
    if on("chunked"):
        print("# §8.2 — chunked bulk engine vs per-element stream")
        if args.quick:
            rows = bench_chunked.main(window=2**8, T=20_000, B=4, pe_T=5_000,
                                      ooo_T=8_000, ooo_horizon=64, ooo_pe_T=600)
        else:
            rows = bench_chunked.main()
        done("chunked", rows)
    if on("keyed"):
        print("# beyond-paper — keyed window store (per-key windows, bulk)")
        if args.quick:
            # K=64k rides along at reduced T so CI exercises the very
            # cliff the fused hot path exists to kill; the window=4096
            # max row rides along (reduced T) so CI gates the flip-sweep
            # acceptance configuration too
            rows = bench_keyed.main(Ks=(256, 4096, 65536), chunks=(1024,),
                                    T=16384, loop_T=400, big_T=8192)
        else:
            rows = bench_keyed.main()
        done("keyed", rows)
    if on("service"):
        from benchmarks import bench_service

        print("# beyond-paper — multi-tenant analytics service (live HTTP)")
        if args.quick:
            rows = bench_service.main(tenants=2, n_per_tenant=6000,
                                      batch=128, universe=256,
                                      quota_rows=1500)
        else:
            rows = bench_service.main()
        done("service", rows)
    if on("roofline"):
        print("# §Roofline — dry-run derived table")
        rows = roofline_table.main()
        done("roofline", rows)

    if args.compare:
        sys.exit(1 if compare_rows(all_rows, args.compare, args.threshold)
                 else 0)


if __name__ == "__main__":
    main()
