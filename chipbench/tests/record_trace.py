#!/usr/bin/env python3
"""Record, on the chip, the trace that ``test_chipbench_trace.py`` reads.

    python chipbench/tests/record_trace.py --seed <n>

Runs ``bids-max.saturate`` for 8 s with 0.25 s of it traced and writes
``data/trace_short.result.json`` (the run's result line) and
``data/trace_short.xplane.pb.gz``: the run's profile less what the
reduction never reads (the ``/host:metadata`` plane of HLO protos, and
each op's metadata and stats beside its name), gzipped.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import run  # noqa: E402


def _varint(b: bytes, i: int) -> tuple:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _enc_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        c, n = n & 0x7F, n >> 7
        out.append(c | 0x80 if n else c)
        if not n:
            return bytes(out)


def _fields(b: bytes):
    """``(field, raw bytes)`` of each field of a protobuf message."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            _, i = _varint(b, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(b, i)
            i += n
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, b[start:i]


def _payload(raw: bytes) -> bytes:
    _, i = _varint(raw, 0)
    n, i = _varint(raw, i)
    return raw[i:i + n]


def _field(f: int, body: bytes) -> bytes:
    return _enc_varint((f << 3) | 2) + _enc_varint(len(body)) + body


def strip(xspace: bytes) -> bytes:
    """XSpace (planes: field 1) without the ``/host:metadata`` plane; in
    every XPlane's event metadata (field 4, a map whose values are field
    2) each XEventMetadata keeps only its id (1), name (2) and child ids
    (6)."""
    out = bytearray()
    for f, raw in _fields(xspace):
        if f != 1:
            out += raw
            continue
        plane = _payload(raw)
        name = next(_payload(r).decode() for f2, r in _fields(plane)
                    if f2 == 2)
        if name == "/host:metadata":
            continue
        kept = bytearray()
        for f2, r2 in _fields(plane):
            if f2 != 4:
                kept += r2
                continue
            entry = bytearray()
            for f3, r3 in _fields(_payload(r2)):
                if f3 == 2:
                    md = b"".join(r4 for f4, r4 in _fields(_payload(r3))
                                  if f4 in (1, 2, 6))
                    entry += _field(2, md)
                else:
                    entry += r3
            kept += _field(4, bytes(entry))
        out += _field(1, bytes(kept))
    return bytes(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(ROOT, "bids-max.saturate")
    with tempfile.TemporaryDirectory() as raw_dir:
        result, _ = run.run_cell(ROOT, spec, seed=args.seed, seconds=8.0,
                                 trace=True, trace_out=raw_dir,
                                 trace_span=0.25)
        (path,) = glob.glob(f"{raw_dir}/**/*.xplane.pb", recursive=True)
        data = strip(pathlib.Path(path).read_bytes())
    (HERE / "data" / "trace_short.xplane.pb.gz").write_bytes(
        gzip.compress(data, mtime=0))
    (HERE / "data" / "trace_short.result.json").write_text(
        json.dumps(result) + "\n")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
