"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds (TPU v5e constants):

    compute    = HLO_FLOPs_per_device   / PEAK_FLOPS_BF16
    memory     = HLO_bytes_per_device   / HBM_BW
    collective = effective_collective_bytes_per_device / ICI_BW

``cost_analysis()`` on the SPMD-partitioned executable reports *per-device*
FLOPs and bytes, so the prompt's ``/ chips`` is already applied.  Collective
bytes are NOT in cost_analysis: we parse the final optimized HLO
(``compiled.as_text()``) and sum result-shape bytes of every collective op,
weighting all-reduce 2× (ring reduce+broadcast phases).  MODEL_FLOPS uses
6·N·D (train) / 2·N·D (inference) with N = (active) parameter count.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

# Effective streaming bandwidth for the memory-bound cost models below.
# The CPU figure is a measured single-core effective bandwidth on large
# gather/scatter+scan patterns (NOT peak DRAM bandwidth — XLA:CPU runs
# these single-threaded).  TPUs are keyed by ``device_kind`` with their
# published HBM bandwidth (Google Cloud "TPU v5e": 819 GB/s).  A backend or
# TPU kind missing here is an error, never a borrowed default.
BACKEND_EFF_BW = {"cpu": 2.0e9}
TPU_HBM_BW = {"TPU v5 lite": HBM_BW}


def effective_bandwidth(backend: Optional[str] = None) -> tuple:
    """``(backend, bytes/s)`` for the cost models; ``backend`` defaults to
    JAX's.  Raises ``ValueError`` for a backend or TPU kind with no entry."""
    import jax

    if backend is None:
        backend = jax.default_backend()
    if backend == "tpu":
        kind = jax.devices("tpu")[0].device_kind
        if kind not in TPU_HBM_BW:
            raise ValueError(f"no HBM bandwidth known for TPU kind {kind!r}; "
                             f"have {sorted(TPU_HBM_BW)}")
        return backend, TPU_HBM_BW[kind]
    if backend not in BACKEND_EFF_BW:
        raise ValueError(f"no effective bandwidth known for backend "
                         f"{backend!r}; have {sorted(BACKEND_EFF_BW)} and tpu")
    return backend, BACKEND_EFF_BW[backend]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVE_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]))\S*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-op-type result bytes from the optimized (post-SPMD) HLO text."""
    by_type: dict[str, dict] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        d = by_type.setdefault(op, {"count": 0, "bytes": 0})
        # `-start/-done` pairs would double count; regex folds them to the
        # same op name, so skip `-done` results (they repeat the shape).
        d["count"] += 1
        d["bytes"] += b
    return by_type


def effective_collective_bytes(by_type: dict) -> float:
    """Ring-model effective wire bytes per device."""
    total = 0.0
    for op, d in by_type.items():
        w = 2.0 if op == "all-reduce" else 1.0
        total += w * d["bytes"]
    return total


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    collectives: dict
    model_flops_total: float  # analytic useful FLOPs for the whole step
    memory_analysis: dict
    skipped: bool = False
    note: str = ""

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def model_flops_per_device(self) -> float:
        return self.model_flops_total / max(self.chips, 1)

    @property
    def useful_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (per device) — remat/redundancy waste."""
        if self.flops_per_device <= 0:
            return 0.0
        return self.model_flops_per_device / self.flops_per_device

    @property
    def roofline_time(self) -> float:
        """Lower-bound step time = max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time / bound time: how close the step is to the
        hardware roofline if perfectly overlapped."""
        t = self.roofline_time
        if t <= 0:
            return 0.0
        return (self.model_flops_per_device / PEAK_FLOPS_BF16) / t

    def to_json(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes": self.collective_bytes,
            "collectives": self.collectives,
            "model_flops_total": self.model_flops_total,
            "memory_analysis": self.memory_analysis,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_fraction": self.useful_fraction,
            "roofline_fraction": self.roofline_fraction,
            "skipped": self.skipped,
            "note": self.note,
        }


def gla_correction(cfg, shape) -> tuple[float, float]:
    """(flops, bytes) missed by cost_analysis for the GLA chunk scan.

    The gated-linear-attention recurrence scans over sequence chunks; XLA
    counts its body once per layer, so (nc - 1) iterations are missing.
    Per-chunk costs (fwd):
        flops ≈ 2·B·H·(L²·(K+V) + 2·L·K·V)
        bytes ≈ 4·B·L·H·(3K + 2V) + 8·B·H·K·V       (f32 activations+state)
    Train steps include remat-recompute + backward ≈ 4× fwd flops / 3× bytes.
    Decode shapes use the per-token sequential step (no scan) — zero
    correction.  These terms are small for both SSM archs (< a few % of the
    projection matmuls) but are included for honesty.
    """
    if not (getattr(cfg, "rwkv", False) or getattr(cfg, "mamba", False)):
        return 0.0, 0.0
    if shape.kind == "decode":
        return 0.0, 0.0
    B, T = shape.global_batch, shape.seq_len
    H = cfg.num_heads
    if cfg.rwkv:
        K = V = cfg.d_model // H
        Lc = cfg.gla_chunk or 64
    else:
        K = cfg.ssm_state
        V = cfg.d_ff // H
        Lc = cfg.gla_chunk or 16
    nc = max(T // Lc, 1)
    missing = max(nc - 1, 0) * cfg.num_layers
    flops_chunk = 2.0 * B * H * (Lc * Lc * (K + V) + 2 * Lc * K * V)
    bytes_chunk = 4.0 * B * Lc * H * (3 * K + 2 * V) + 8.0 * B * H * K * V
    if shape.kind == "train":
        flops_chunk *= 4.0
        bytes_chunk *= 3.0
    return missing * flops_chunk, missing * bytes_chunk


def model_flops(cfg, shape) -> float:
    """Analytic useful FLOPs for one step of (cfg, shape).

    train: 6·N_active·D;  prefill: 2·N_active·D;  decode: 2·N_active·B
    plus attention-context FLOPs for decode (KV reads are memory-side).
    """
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def keyed_update_cost(
    chunk: int,
    window: int,
    *,
    value_bytes: int = 4,
    probes: int = 32,
    backend: Optional[str] = None,
) -> dict:
    """Memory-bound roofline for one keyed ``update_chunk`` dispatch.

    Models the MANDATORY steady-state traffic of
    :meth:`repro.core.keyed.KeyedWindowStore.update_chunk` — every term is
    per-chunk, none scales with the slot pool (the donated carry scatter is
    in-place, so the resident (slots, h) state contributes only the touched
    rows):

      * sort + segment bookkeeping: ``~log2(C)`` comparison passes over the
        (C,) key lane plus a handful of (C,) index/mask lanes;
      * directory probing: one ``(C, probes)`` int32 gather;
      * carry traffic: ONE (C, h) row gather + ONE (C, h) batched scatter;
      * segmented two-stacks flip sweep: TWO segmented pair-operator scans
        (block prefix + block suffix, ``log2(C)`` passes each) — the
        constant-combine replacement for the retired ``log2(W)`` doubling
        range-fold table, so per-chunk cost no longer carries a term that
        grows with the window;
      * refresh suffix scan: one more ``log2(C)`` pair-operator pass.

    Returns ``{"bytes_per_chunk", "t_memory", "items_per_s_bound", "bw",
    "backend", "stages"}``.  The bound is what a perfectly-fused
    implementation hitting effective bandwidth would sustain; ``measured /
    items_per_s_bound`` is the roofline-relative fraction benchmark rows
    report.  ``stages`` maps pipeline stage → modeled bytes (sort / probe /
    admit / sweep / scatter) — :meth:`repro.obs.trace.TraceRecorder
    .add_stage_spans` uses it to apportion a measured chunk span into
    per-stage sub-spans.
    """
    import math

    backend, bw = effective_bandwidth(backend)
    C = int(chunk)
    h = max(int(window) - 1, 0)
    lg_c = max(math.ceil(math.log2(max(C, 2))), 1)

    b_sort = 2.0 * C * 4 * lg_c                 # argsort passes (int32 keys)
    b_lanes = 10.0 * C * 4                      # segment/index/mask lanes
    b_probe = C * probes * 4.0                  # directory gather
    b_carry = 2.0 * C * h * value_bytes         # row gather + batched scatter
    # flip sweep (block prefix + block suffix) + refresh suffix scan: three
    # segmented pair-op scans, constant in W (the log2(W) doubling-table
    # term is retired)
    b_sscan = 3.0 * 3.0 * C * lg_c * (value_bytes + 4)
    total = b_sort + b_lanes + b_probe + b_carry + b_sscan
    t_mem = total / bw
    return {
        "bytes_per_chunk": total,
        "t_memory": t_mem,
        "items_per_s_bound": C / t_mem if t_mem > 0 else 0.0,
        "bw": bw,
        "backend": backend,
        # hot-path stage names (update_chunk order); carry traffic split
        # between its gather (admit) and scatter halves
        "stages": {
            "sort": b_sort + b_lanes,
            "probe": b_probe,
            "admit": b_carry / 2.0,
            "sweep": b_sscan,
            "scatter": b_carry / 2.0,
        },
    }


def eventtime_release_cost(
    chunk: int,
    capacity: int,
    *,
    distance: int = 0,
    value_bytes: int = 4,
    batch: int = 1,
    backend: Optional[str] = None,
) -> dict:
    """Memory-bound roofline for one event-time ``process_chunk`` dispatch.

    Models the steady-state traffic of
    :class:`repro.core.event_time.EventTimeChunkedStream` per chunk of P
    released rows merged into a W-row window (``M = W + P`` merged
    positions, ``batch`` value lanes per position).  The release stage is
    DISTANCE-AWARE (the disorder-adaptive path of
    :mod:`repro.core.ooo_index`): ``distance`` is the maximum out-of-order
    displacement ``d`` of the chunk's rows —

      * ``d = 0`` (the ``lax.cond`` fast branch): no sort at all, just the
        comparison-free ``compact_perm`` index build plus its gather —
        2 passes over the (P,) pending lanes;
      * ``d > 0``: a stable sort whose comparison depth scales with the
        disordered region ``min(P, 2d)`` — ``log2`` passes over (P,)
        lanes plus the sorted gather (cf. the d-bounded costs of
        arXiv 1810.11308 / 2307.11210);
      * merge gather dual: merged timestamps + aggregates assembled by two
        position gathers (no scatters — see the module docstring);
      * flip boundary orbit: gather-only binary lifting, ``log2(M)``
        levels of (M,) int32 hops;
      * flip sweep: segmented suffix + running prefix ``associative_scan``
        over (M,) pair lanes — constant combines per element, NO term
        grows with the horizon (the retired table paid ``log2(W + C)``
        per element);
      * eviction re-gather of the W-row window.

    Same return shape as :func:`keyed_update_cost`; ``items_per_s_bound``
    counts P·batch items per dispatch.  ``stages["release"]`` holds
    whichever release term applies (compact or sort).
    """
    import math

    backend, bw = effective_bandwidth(backend)
    P = int(chunk)
    W = int(capacity)
    M = W + P
    d = max(int(distance), 0)
    vb = value_bytes * max(int(batch), 1)
    lg_m = max(math.ceil(math.log2(max(M, 2))), 1)

    if d == 0:
        # compact_perm: index arithmetic + one permutation gather
        b_release = 2.0 * P * (vb + 4)
    else:
        region = min(P, max(2 * d, 2))
        lg_d = max(math.ceil(math.log2(region)), 1)
        b_release = 2.0 * P * 4 * lg_d + P * (vb + 4)
    b_merge = 3.0 * M * (vb + 4)               # gather-dual ts+agg assembly
    b_orbit = 2.0 * M * 4 * lg_m               # binary-lifting hop levels
    b_sweep = 4.0 * M * (vb + 4)               # seg suffix + prefix scans
    b_evict = 2.0 * W * (vb + 4)               # window re-gather
    total = b_release + b_merge + b_orbit + b_sweep + b_evict
    t_mem = total / bw
    items = P * max(int(batch), 1)
    return {
        "bytes_per_chunk": total,
        "t_memory": t_mem,
        "items_per_s_bound": items / t_mem if t_mem > 0 else 0.0,
        "bw": bw,
        "backend": backend,
        "stages": {
            "release": b_release,
            "merge": b_merge,
            "orbit": b_orbit,
            "sweep": b_sweep,
            "evict": b_evict,
        },
    }


def keyed_horizon_cost(
    chunk: int,
    window: int,
    *,
    value_bytes: int = 4,
    probes: int = 32,
    backend: Optional[str] = None,
) -> dict:
    """Memory-bound roofline for one keyed ``update_chunk`` dispatch in
    event-time ``horizon=`` mode — :func:`keyed_update_cost` plus the two
    extra traffic terms the mode adds:

      * lane timestamps: ONE (C, h) f32 ``carry_ts`` row gather + ONE
        batched scatter (the ts mirror of the carry traffic);
      * span-start finger search: ``bit_length(C)`` rounds of one (C,)
        timestamp gather each (:func:`repro.core.ooo_index
        .seg_bounded_search`).

    Same return shape; ``stages`` gains ``lane_ts`` / ``search``.
    """
    import math

    base = keyed_update_cost(
        chunk, window, value_bytes=value_bytes, probes=probes,
        backend=backend,
    )
    C = int(chunk)
    h = max(int(window) - 1, 0)
    lg_c = max(math.ceil(math.log2(max(C, 2))), 1)
    b_ts = 2.0 * C * h * 4                     # carry_ts gather + scatter
    b_search = C * 4.0 * (lg_c + 1)            # finger-search gather rounds
    total = base["bytes_per_chunk"] + b_ts + b_search
    t_mem = total / base["bw"]
    return {
        "bytes_per_chunk": total,
        "t_memory": t_mem,
        "items_per_s_bound": C / t_mem if t_mem > 0 else 0.0,
        "bw": base["bw"],
        "backend": base["backend"],
        "stages": dict(base["stages"], lane_ts=b_ts, search=b_search),
    }


def save_roofline(r: Roofline, path: str):
    with open(path, "w") as f:
        json.dump(r.to_json(), f, indent=2)


def load_roofline(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
