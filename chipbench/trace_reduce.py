"""Profiler trace → device intervals by program and kernel name.

Reads one ``*.xplane.pb`` with :class:`jax.profiler.ProfileData` (the
layout of a TPU trace under jax 0.9):

* plane ``/device:TPU:<n>``, line ``XLA Modules``: one event per program
  execution, named ``<HLO module>(<fingerprint>)``, e.g.
  ``jit_update_chunk(5981115695802921102)``;
* same plane, line ``XLA Ops``: one event per HLO op, named by the op's
  HLO text, ``%<instruction> = <shape> <opcode>(<operands>), ...``; a
  Pallas kernel is a ``custom-call`` whose text names
  ``custom_call_target="tpu_custom_call"`` and whose instruction is the
  kernel's name (``seg_suffix_scan_pallas.1``);
* planes ``/host:*``: host events per thread, among them the harness's
  annotations ``chipbench.trace_open`` / ``chipbench.trace_close``, which
  bound the traced window.

``reduce_file`` returns ``window_s``; ``busy_s`` (the union of the
``XLA Ops`` intervals inside the window, averaged over the device planes);
per program, its executions that lie wholly inside the window and their
device seconds; per Pallas kernel, its calls, seconds and HLO text; and
``breakdown`` (the ops that took most device time, and the longest idle
gaps named by the host event that covered most of each).  Nothing here
imports the program.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPEN, CLOSE = "chipbench.trace_open", "chipbench.trace_close"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def module_name(event_name: str) -> str:
    """``jit_update_chunk(5981115695802921102)`` → ``jit_update_chunk``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def instruction(op_text: str) -> str:
    """``%fusion.72 = s32[...] fusion(...)`` → ``fusion.72``."""
    head = op_text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def kernel_name(inst: str) -> str:
    """``seg_suffix_scan_pallas.1`` → ``seg_suffix_scan_pallas``."""
    return re.sub(r"\.\d+$", "", inst)


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(path: str) -> dict:
    """The raw intervals of one xplane file (ns, the trace's own clock)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    devices, host, marks = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                "ops": _events(lines["XLA Ops"]) if "XLA Ops" in lines else [],
                "modules": (_events(lines["XLA Modules"])
                            if "XLA Modules" in lines else [])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for name, t, d in _events(ln):
                    if name in (OPEN, CLOSE):
                        marks.setdefault(name, t)
                    else:
                        host.append((ln.name, name, t, d))
    if OPEN not in marks or CLOSE not in marks:
        raise ValueError("trace lacks the chipbench open/close annotations")
    return {"devices": devices, "host": host,
            "window": (marks[OPEN], marks[CLOSE])}


def union(spans) -> list:
    """Merged ``[start, end]`` of ``(start, end)`` spans."""
    merged = []
    for a, b in sorted(spans):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def gaps(merged, lo, hi) -> list:
    """Idle ``(start, end)`` between ``merged`` busy spans in ``[lo, hi]``."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(host, a, b) -> str:
    """The host event (``thread: name``) that covers most of ``[a, b]``
    (of those within a tenth of the most, the shortest: the innermost
    span), or ``unattributed``."""
    cover = [(min(t + d, b) - max(t, a), d, f"{th or 'thread'}: {name}")
             for th, name, t, d in host]
    cover = [c for c in cover if c[0] > 0]
    if not cover:
        return "unattributed"
    most = max(c[0] for c in cover)
    return min((c for c in cover if c[0] >= 0.9 * most),
               key=lambda c: c[1])[2]


def _module_of(ops, runs):
    """Each op's program, by time containment in the program runs."""
    runs = sorted(runs, key=lambda r: r[1])
    out, j = [], 0
    for name, t, d in ops:
        while j < len(runs) and runs[j][1] + runs[j][2] < t:
            j += 1
        inside = j < len(runs) and runs[j][1] <= t
        out.append(module_name(runs[j][0]) if inside else "?")
    return out


def reduce(raw: dict) -> dict:
    lo, hi = raw["window"]
    devs = sorted(raw["devices"])
    busy, merged = [], []
    for dname in devs:
        merged.append(union((max(t, lo), min(t + d, hi)) for _, t, d in
                            raw["devices"][dname]["ops"]
                            if t + d > lo and t < hi))
        busy.append(sum(b - a for a, b in merged[-1]) * 1e-9)
    out = {"window_s": (hi - lo) * 1e-9,
           "busy_s": sum(busy) / len(busy) if busy else 0.0,
           "programs": {}, "kernels": {},
           "breakdown": {"device_ops": [], "idle_gaps": []}}
    if not devs:
        return out
    dev = raw["devices"][devs[0]]
    ops = sorted((o for o in dev["ops"] if lo <= o[1] and o[1] + o[2] <= hi),
                 key=lambda o: o[1])
    runs = [m for m in dev["modules"] if lo <= m[1] and m[1] + m[2] <= hi]
    for name, _, d in runs:
        p = out["programs"].setdefault(module_name(name),
                                       {"runs": 0, "seconds": 0.0})
        p["runs"] += 1
        p["seconds"] += d * 1e-9
    top = defaultdict(float)
    for (text, _, d), mod in zip(ops, _module_of(ops, dev["modules"])):
        inst = instruction(text)
        top[f"{mod}/{inst}"] += d * 1e-9
        if KERNEL_MARK in text:
            k = out["kernels"].setdefault(kernel_name(inst), {
                "calls": 0, "seconds": 0.0, "hlo": text})
            k["calls"] += 1
            k["seconds"] += d * 1e-9
    out["breakdown"]["device_ops"] = [
        [k, v] for k, v in sorted(top.items(), key=lambda kv: -kv[1])[:10]]
    host = [h for h in raw["host"] if h[2] < hi and h[2] + h[3] > lo
            and h[3] < hi - lo]
    longest = sorted(gaps(merged[0], lo, hi), key=lambda g: g[0] - g[1])[:10]
    out["breakdown"]["idle_gaps"] = [[name_gap(host, a, b), (b - a) * 1e-9]
                                     for a, b in longest]
    return out


def reduce_file(path: str) -> dict:
    return reduce(load(path))


def reduce_dir(trace_dir: str) -> dict:
    return reduce_file(find_xplane(trace_dir))
