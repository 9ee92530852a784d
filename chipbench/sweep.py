#!/usr/bin/env python3
"""Find the knee of an open-loop mix: the highest offered rate whose
backlog does not grow over a window.

    python chipbench/sweep.py --workload bids-max.steady \\
        --rates 24000,28000,32000 --seconds 10 --seed 1

Runs the cell once per rate, in one process, with the mix's
``rate_events_per_s`` replaced, and prints one JSON line per rate: the
offered and the drained rate, the backlog at the window's close, and the
due → queryable latency in the first and the second half of the window
(a growing backlog shows as a second half slower than the first).  The
knee goes into the mix's file by hand; the benchmark's runs never sweep.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import run, window  # noqa: E402


def summary(rec: dict, rate: float) -> dict:
    b, vis = rec["batches"], rec["visible"]
    t0, t1 = rec["t_open"], rec["t_close"]
    ok = window.due_in_window(b, rec) & (b[:, 4] == 200)
    lat = (vis - b[:, 1]) * 1e3
    half = (t0 + t1) / 2
    out = {"offered_events_per_s": rate,
           "drained_events_per_s": float(b[(vis > t0) & (vis <= t1), 6].sum())
           / rec["seconds"],
           "backlog_rows_at_close": rec["stats_close"]["pending_rows"],
           "chunk_fill_pct": None}
    a, c = rec["stats_open"], rec["stats_close"]
    if c["chunks"] > a["chunks"]:
        out["chunk_fill_pct"] = 100.0 * (c["drained_rows"] - a["drained_rows"]) \
            / ((c["chunks"] - a["chunks"]) * rec["chunk"])
    for label, sel in (("first_half", ok & (b[:, 1] < half)),
                       ("second_half", ok & (b[:, 1] >= half))):
        out[label] = {"p50_ms": window.percentile(lat[sel], 50),
                      "p95_ms": window.percentile(lat[sel], 95),
                      "batches": int(sel.sum())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = run.load_cell(ROOT, args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        spec_r = dict(spec, mix=dict(spec["mix"], rate_events_per_s=rate))
        result, rec = run.run_cell(ROOT, spec_r, seed=args.seed,
                                   seconds=args.seconds, trace=False)
        line = summary(rec, rate)
        line["correct"] = result["correct"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
