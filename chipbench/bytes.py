"""Least bytes and operations a kernel call needs, from its shapes.

A roofline share is the least time the chip could take for the call,
``max(ops / peak ops, bytes / peak bandwidth)``, over the kernel's
measured device time.  ``peaks(kind)`` reads ``peaks.json``; a device
kind that is not there is an error.
"""

from __future__ import annotations

import json
import math
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def seg_scan(rows: int, length: int, itemsize: int = 4,
             flag_itemsize: int = 4) -> dict:
    """``seg_suffix_scan_pallas`` / ``seg_prefix_scan_pallas`` on a
    ``(rows, length)`` operand: read the values and the segment flags
    once, write the scanned values once; a segmented scan needs one
    combine per element that has a neighbour in its row."""
    n = rows * length
    return {"bytes": n * (2 * itemsize + flag_itemsize),
            "ops": rows * max(length - 1, 0)}


def least_seconds(cost: dict, peak: dict) -> tuple:
    """``(seconds, bound)``: the larger of the memory time and the
    compute time at the peaks (integer combines run on the vector unit,
    whose rate the table does not give; the bf16 peak stands in for it,
    so the compute time is a lower bound)."""
    t_mem = cost["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = cost["ops"] / peak["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")


def roofline_percent(cost: dict, peak: dict, seconds: float) -> float:
    if not seconds > 0 or math.isnan(seconds):
        raise ValueError(f"kernel time must be > 0, got {seconds}")
    return 100.0 * least_seconds(cost, peak)[0] / seconds
