#!/usr/bin/env python3
"""Readings behind the limits of ``correct``: the program over many seeds
and the controls, in one process.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 5

For each seed: one run of the cell as ``run.py`` makes it (set-up, window,
the check), then the same check with the service's answers replaced by
each control's.  A control is the plain reference put in the program's
place with one stated guarantee broken:

* ``stale``: every tenant's last acknowledged batch is missing, as from a
  read that answers before the final drain is synced (breaks "every
  acknowledged row is queryable after flush()");
* ``narrow``: each running fold is rounded to the type below the stated
  one, as the fold's file says (``narrow``: int16 for int32, float16 for
  float32).

One JSON line per seed: the program's checks and each control's.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import reference, run  # noqa: E402


def _answers(rec: dict, acked: list, narrow: bool) -> list:
    cfg = rec["config"]["service"]
    out = []
    for i, stream in enumerate(rec["streams"]):
        k, t, x = stream.rows(0, acked[i])
        out.append(reference.key_folds(
            rec["fold"], k, t, x, rec["queried"][i], window=cfg["window"],
            horizon=cfg["horizon"], narrow=narrow))
    return out


def stale(rec: dict) -> list:
    """The reference without each tenant's last acknowledged batch."""
    b = rec["batches"]
    acked = []
    for i, a in enumerate(rec["acked_rows"]):
        mine = b[(b[:, 0] == i) & (b[:, 4] == 200)]
        acked.append(a - int(mine[-1, 6]) if len(mine) else a)
    return _answers(rec, acked, narrow=False)


def narrow(rec: dict) -> list:
    """The reference in the type below the stated one."""
    return _answers(rec, rec["acked_rows"], narrow=True)


CONTROLS = {"stale": stale, "narrow": narrow}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        result, rec = run.run_cell(ROOT, spec, seed=seed,
                                   seconds=args.seconds, trace=False)
        line = {"seed": seed, "correct": result["correct"],
                "program": rec["checks"],
                "queried_keys": sum(len(q) for q in rec["queried"])}
        for name, fn in CONTROLS.items():
            line[name] = run.compare(dict(rec, got=fn(rec)))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
