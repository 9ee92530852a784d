"""``seg_scan_roofline``: the ``seg_suffix_scan_pallas`` kernel's share of
its roofline, in percent: the least time its bytes take at the chip's HBM
bandwidth (memory bound: a segmented scan does one combine per element),
over its mean device time per call in the traced window.  The shapes come
from the kernel's HLO text in the trace."""

import re

from chipbench import bytes as kbytes

KERNEL = "seg_suffix_scan_pallas"
ITEMSIZE = {"s32": 4, "u32": 4, "f32": 4, "bf16": 2, "s8": 1, "pred": 1}


def read(rec):
    k = (rec.get("trace") or {}).get("kernels", {}).get(KERNEL)
    if not k or not k["calls"] or k["seconds"] <= 0:
        return None
    # %seg_suffix_scan_pallas.1 = s32[1,4096]{...} custom-call(s32[1,4096]
    #   {...} %values, s32[1,4096]{...} %flags), ...
    shapes = re.findall(r"\b([a-z]+\d*)\[(\d+),(\d+)\]", k["hlo"])
    (out_t, rows, length), _, (flag_t, _, _) = shapes[:3]
    cost = kbytes.seg_scan(int(rows), int(length), ITEMSIZE[out_t],
                           ITEMSIZE[flag_t])
    peak = kbytes.peaks(rec["device_kind"])
    return kbytes.roofline_percent(cost, peak, k["seconds"] / k["calls"])
