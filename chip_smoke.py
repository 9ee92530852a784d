#!/usr/bin/env python3
"""Drive the system's main path once on a TPU and check every result.

    python chip_smoke.py [--seed N]           # one chip: the phases below
    python chip_smoke.py --four-chips         # four chips: the sharded phase

Everything runs in this one process (the HTTP server on its threads) and
all data is generated from ``--seed``.  Phases on one chip:

* ``service/<monoid>`` for ``sum_i32`` and ``max_i32``: an
  :class:`~repro.service.AnalyticsService` with 2**20 slots (window 256,
  event-time horizon 64, chunk 4096) loads four Zipf tenants through
  ``ingest``, then takes a few batches through ``POST /ingest``.  The folds
  ``GET /query`` returns for each tenant's hottest keys, a seeded random
  sample and a few never-seen keys must equal the plain per-key reference
  (:mod:`repro.core.reference`) bit for bit.
* ``chunked/<op>``: :class:`~repro.core.chunked.ChunkedStream` over an
  (2**20 rows x 8 lanes) stream, window 1024, against a numpy window fold.
* ``eventtime/<monoid>/d=<d>``: :class:`~repro.core.event_time
  .EventTimeChunkedStream`, horizon 1024, chunk 4096, out-of-order
  distance 0 and 16, against :func:`~repro.core.event_time
  .in_order_reference`.

``--four-chips`` runs only ``sharded``: :class:`~repro.core.keyed
.ShardedKeyedStore` over a 4-chip mesh against one
:class:`~repro.core.keyed.KeyedChunkedStream` on chip 0, fed the same
chunks.

The keyed and chunked phases compile their step ahead of time and require
the Pallas kernels in it as ``tpu_custom_call``s, so a kernel that fell
back to interpret mode or to the lax path fails the run.  References run
on the host CPU device.  Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {...}}`` only when every phase passed; the exit
code is non-zero, with no such line, when any phase failed or JAX finds no
TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import re
import sys
import time
import traceback
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The deployment (see PERF.md "Cells"): 2**20 live-key slots of the
# service's default window and horizon, four tenants of Zipf keys.
SERVICE = dict(slots=2**20, window=256, horizon=64.0, chunk=4096, tenants=4,
               rows_per_tenant=1_000_000, universe=2**18, zipf_a=1.1,
               http_batches=4, hot=16, sample=64, unseen=4)
CHUNKED = dict(rows=2**20, lanes=8, window=1024, chunk=65536)
EVENTTIME = dict(rows=16384, lanes=8, horizon=1024.0, chunk=4096)
SHARDED = dict(shards=4, slots_per_shard=2**19, window=256, horizon=64.0,
               chunk=4096, keys=2**20, zipf_rows=2**20, zipf_a=1.1)


def log(rec: dict) -> None:
    print(json.dumps(rec, default=str), flush=True)


def pallas_kernels(compiled) -> list:
    """Names of the Pallas kernels compiled into a program as
    ``tpu_custom_call``s (from the compiled HLO's op metadata)."""
    names = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"jit\((\w+)\)/pallas_call", line)
            names.append(m.group(1) if m else "?")
    return sorted(names)


def memory(devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    return {"peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
            "bytes_in_use": [s.get("bytes_in_use") for s in stats]}


def host_device():
    """Context placing reference computations on the host CPU device (the
    default device when no CPU backend is available)."""
    import jax

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except Exception:  # no CPU backend in this process
        return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# service: AnalyticsService + HTTP at 2**20 slots
# ---------------------------------------------------------------------------


def _reference_fold(monoid, keys, xs, ts, key, window, horizon):
    """The per-key reference's fold at ``key``'s last row.  The reference
    keeps each key's last ``window`` rows, so replaying only those gives
    its final output exactly."""
    from repro.core.reference import per_key_horizon_reference

    rows = np.nonzero(keys == key)[0][-window:]
    ys = per_key_horizon_reference(
        monoid, keys[rows], xs[rows].tolist(), ts[rows], window, horizon
    )
    return np.asarray(monoid.lower(ys[-1])).tolist()


def _http(url: str, doc=None) -> tuple:
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(url, data,
                                 {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=600) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def compile_keyed_step(monoid_name: str, *, slots, window, horizon, chunk,
                       **_) -> dict:
    """Compile the service's keyed chunk step ahead of time — the same
    program the consumer thread later dispatches (and then reads back from
    the compilation cache) — and list its Pallas kernels."""
    import jax
    import jax.numpy as jnp

    from repro.core.keyed import KeyedWindowStore
    from repro.core.monoids import get_monoid

    store = KeyedWindowStore(get_monoid(monoid_name), window, slots,
                             horizon=horizon)
    row = lambda dt: jax.ShapeDtypeStruct((chunk,), dt)  # noqa: E731
    t0 = time.perf_counter()
    compiled = jax.jit(store.update_chunk).lower(
        jax.eval_shape(store.init_state), row(jnp.int32), row(jnp.int32),
        row(jnp.float32), row(jnp.bool_),
    ).compile()
    seconds = time.perf_counter() - t0
    kernels = pallas_kernels(compiled)
    return {"compile_s": seconds, "kernels": kernels,
            "tpu_custom_call": bool(kernels)}


def service_phase(monoid_name: str, seed: int, step: dict, *, slots, window,
                  horizon, chunk, tenants, rows_per_tenant, universe, zipf_a,
                  http_batches, hot, sample, unseen) -> dict:
    """``step`` is :func:`compile_keyed_step`'s record for this monoid."""
    import jax

    from repro.core.monoids import get_monoid
    from repro.data.stream import MultiTenantEventStream
    from repro.service import AnalyticsService, ServiceConfig, ServiceHTTPServer

    monoid = get_monoid(monoid_name)
    cfg = ServiceConfig(
        monoid=monoid_name, window=window, horizon=horizon, slots=slots,
        chunk=chunk, max_batch=chunk,
        # nothing is throttled or shed: every row is accepted
        quota_rows_per_s=1e15, quota_burst=1e15,
        tenant_queue_batches=1 << 30, global_rows_hw=1 << 40,
    )
    rec = {"monoid": monoid_name, "slots": slots, "window": window,
           "horizon": horizon, "chunk": chunk, "tenants": tenants, **step}
    # the carry refresh takes the seg_scan suffix kernel on every path
    kernels_ok = "seg_suffix_scan_pallas" in step["kernels"]

    gen = MultiTenantEventStream(tenants, rows_per_tenant, universe,
                                 zipf_a=zipf_a, seed=seed)
    data = [gen.arrival_host(i) for i in range(tenants)]
    names = [f"tenant-{i}" for i in range(tenants)]
    n_http = http_batches * chunk
    n_bulk = rows_per_tenant - n_http  # per tenant, through ingest()
    codes = []

    svc = AnalyticsService(cfg).start()
    try:
        rec["state_bytes"] = sum(a.nbytes for a in jax.tree.leaves(svc._state))
        # the first drain carries the first dispatch of every program
        t0 = time.perf_counter()
        codes.append(svc.ingest(names[0], *(a[:chunk] for a in data[0]))[0])
        flushed = svc.flush(timeout=900)
        rec["first_drain_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        for lo in range(0, n_bulk, chunk):
            for i in range(tenants):
                if lo or i:
                    batch = (a[lo:min(lo + chunk, n_bulk)] for a in data[i])
                    codes.append(svc.ingest(names[i], *batch)[0])
        flushed &= svc.flush(timeout=900)
        rec["ingest_s"] = time.perf_counter() - t0
        rec["ingest_rows"] = tenants * n_bulk - chunk
        counters = jax.device_get(svc._engine.store.counters(svc._state))
        rec.update({k: int(v) for k, v in counters.items()})
        rec["live_fraction"] = rec["n_live"] / slots

        with ServiceHTTPServer(svc) as srv:
            t0 = time.perf_counter()
            for i in range(tenants):
                for lo in range(rows_per_tenant - n_http, rows_per_tenant,
                                chunk):
                    k, t, x = (a[lo:lo + chunk] for a in data[i])
                    codes.append(_http(f"{srv.url}/ingest", {
                        "tenant": names[i], "keys": k.tolist(),
                        "ts": t.tolist(), "values": x.tolist()})[0])
            flushed &= svc.flush(timeout=900)
            rec["http_ingest_rows"] = tenants * n_http
            rec["http_ingest_s"] = time.perf_counter() - t0

            rng = np.random.default_rng((seed, 5))
            queries, replies = [], []
            t0 = time.perf_counter()
            for i in range(tenants):
                keys = data[i][0]
                uniq, counts = np.unique(keys, return_counts=True)
                hot_keys = uniq[np.argsort(-counts, kind="stable")][:hot]
                rest = np.setdiff1d(uniq, hot_keys)
                picked = rng.choice(rest, size=min(sample, rest.size),
                                    replace=False)
                never = np.arange(universe, universe + unseen)
                q = np.concatenate([hot_keys, np.sort(picked), never])
                code, snap = _http(f"{srv.url}/query?tenant={names[i]}&keys="
                                   + ",".join(str(int(v)) for v in q))
                codes.append(code)
                queries.append(q)
                replies.append(snap)
            rec["query_s"] = time.perf_counter() - t0
        svc.stop()
    finally:
        if svc._thread is not None:
            svc.stop(drain=False)

    # the plain reference, on the host
    t0 = time.perf_counter()
    mismatches, n_keys = [], 0
    with host_device():
        ident = np.asarray(monoid.lower(monoid.identity())).tolist()
        for i in range(tenants):
            keys, ts, xs = data[i]
            for key in queries[i]:
                n_keys += 1
                got = replies[i]["keys"][str(int(key))]
                if key >= universe:
                    want = {"found": False, "fold": ident}
                else:
                    want = {"found": True, "fold": _reference_fold(
                        monoid, keys, xs, ts, key, window, horizon)}
                if got != want:
                    mismatches.append([names[i], int(key), got, want])
    rec["reference_s"] = time.perf_counter() - t0
    rec["query_keys"] = n_keys
    rec["mismatches"] = len(mismatches)
    rec["first_mismatches"] = mismatches[:5]
    rec["non_200"] = [c for c in codes if c != 200]
    rec["flushed"] = bool(flushed)
    rec["match"] = not mismatches and n_keys > 0
    rec["ok"] = (rec["match"] and kernels_ok and flushed
                 and not rec["non_200"] and rec["n_dropped"] == 0
                 and rec["n_failed"] == 0)
    return rec


# ---------------------------------------------------------------------------
# chunked: ChunkedStream with the sliding_window / suffix_scan kernels
# ---------------------------------------------------------------------------


def window_fold_reference(op: str, xs: np.ndarray, window: int) -> np.ndarray:
    """Front-truncated per-lane window fold along axis 0, in numpy: sums by
    exact int64 prefix differences, maxima by doubling spans."""
    if op == "sum":
        c = np.cumsum(xs.astype(np.int64), axis=0)
        out = c.copy()
        out[window:] -= c[:-window]
        return out.astype(xs.dtype)
    span, m = 1, xs.copy()  # m[t] = max(xs[t-span+1 .. t])
    while 2 * span <= window:
        nxt = m.copy()
        nxt[span:] = np.maximum(m[span:], m[:-span])
        m, span = nxt, 2 * span
    out = m.copy()  # overlap two spans to cover exactly `window` rows
    d = window - span
    if d:
        out[d:] = np.maximum(m[d:], m[:-d])
    return out


def chunked_phase(op: str, seed: int, *, rows, lanes, window, chunk) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import monoids
    from repro.core.chunked import ChunkedStream

    monoid = {"sum": monoids.sum_monoid, "max": monoids.max_monoid}[op](
        jnp.float32)
    rec = {"op": op, "rows": rows, "lanes": lanes, "window": window,
           "chunk": chunk}
    xs = np.random.default_rng((seed, 11)).integers(
        -9, 9, (rows, lanes)).astype(np.float32)  # int-valued: exact in f32
    eng = ChunkedStream(monoid, window, chunk)
    t0 = time.perf_counter()
    compiled = jax.jit(eng.chunk_fn).lower(
        eng.init_carry(lanes), jax.ShapeDtypeStruct((chunk, lanes), jnp.float32),
        jax.ShapeDtypeStruct((chunk,), jnp.bool_),
    ).compile()
    rec["compile_s"] = time.perf_counter() - t0
    rec["kernels"] = pallas_kernels(compiled)
    rec["tpu_custom_call"] = bool(rec["kernels"])
    kernels_ok = {"sliding_window_pallas",
                  "suffix_scan_pallas"} <= set(rec["kernels"])

    x_dev = jnp.asarray(xs)
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        ys = jax.block_until_ready(eng.stream(x_dev))
        rec[f"{run}_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = window_fold_reference(op, xs, window)
    rec["reference_s"] = time.perf_counter() - t0
    got = np.asarray(ys)
    rec["mismatches"] = int(np.sum(got != ref))
    rec["match"] = got.shape == ref.shape and rec["mismatches"] == 0
    rec["ok"] = rec["match"] and kernels_ok
    return rec


# ---------------------------------------------------------------------------
# eventtime: EventTimeChunkedStream (pure lax, no kernel)
# ---------------------------------------------------------------------------


def eventtime_phase(monoid_name: str, distance: int, seed: int, *, rows,
                    lanes, horizon, chunk) -> dict:
    import jax

    from repro.core.event_time import EventTimeChunkedStream, in_order_reference
    from repro.core.monoids import get_monoid
    from repro.data.stream import DisorderedEventStream

    monoid = get_monoid(monoid_name)
    slack = float(max(distance, 16))  # the bench_eventtime.py disorder sweep
    rec = {"monoid": monoid_name, "distance": distance, "rows": rows,
           "lanes": lanes, "horizon": horizon, "chunk": chunk,
           "kernels": [], "tpu_custom_call": False}
    ats, axs = DisorderedEventStream(
        rows, lanes, mean_gap=1.0, disorder=0.0 if distance == 0 else 0.5,
        slack=slack, integer_values=True, seed=seed,
    ).arrival()
    eng = EventTimeChunkedStream(
        monoid, horizon, slack=slack, chunk=chunk,
        capacity=2 * int(horizon) + 64, buffer=max(4 * int(slack) + 16, 64),
    )
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        res = eng.stream(ats, axs)
        jax.block_until_ready(jax.tree.leaves(res.state))
        rec[f"{run}_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    with host_device():
        ref_ts, ref_ys = in_order_reference(
            monoid, np.asarray(ats), np.asarray(axs), horizon)
    rec["reference_s"] = time.perf_counter() - t0
    rec["released"] = int(res.ts.shape[0])
    rec["n_late"], rec["n_dropped"] = res.n_late, res.n_dropped
    rec["match"] = (np.array_equal(res.ts, ref_ts)
                    and np.array_equal(np.asarray(res.ys), np.asarray(ref_ys)))
    rec["ok"] = rec["match"] and res.n_late == 0 and res.n_dropped == 0
    return rec


# ---------------------------------------------------------------------------
# sharded (--four-chips): ShardedKeyedStore vs one store on chip 0
# ---------------------------------------------------------------------------


def sharded_phase(seed: int, *, shards, slots_per_shard, window, horizon,
                  chunk, keys, zipf_rows, zipf_a):
    import jax

    from repro.core.keyed import KeyedChunkedStream, ShardedKeyedStore
    from repro.core.monoids import get_monoid
    from repro.data.stream import KeyedEventStream

    devices = jax.devices()[:shards]
    monoid_name = "max_i32"  # non-invertible: the flip-sweep path
    monoid = get_monoid(monoid_name)
    rec = {"monoid": monoid_name, "shards": shards,
           "slots_per_shard": slots_per_shard, "window": window,
           "horizon": horizon, "chunk": chunk}
    mesh = jax.make_mesh((shards,), ("data",), devices=devices)
    sh = ShardedKeyedStore(monoid, window, slots_per_shard, mesh, "data",
                           horizon=horizon)
    state = jax.block_until_ready(sh.init_state())
    leaves = jax.tree.leaves(state)
    stack_bytes = sum(a.nbytes for a in leaves)
    peaks = memory(devices)["peak_bytes_in_use"]
    rec["stack_bytes"] = stack_bytes
    rec["peak_after_init"] = peaks
    rec["own_device"] = all(
        sorted(s.device.id for s in a.addressable_shards)
        == sorted(d.id for d in devices)
        and all(s.data.shape[0] == 1 for s in a.addressable_shards)
        for a in leaves
    )
    # no device ever held more than its own shard (plus slack), let alone
    # the whole stack
    rec["never_whole_stack"] = all(p is not None and p < stack_bytes / 2
                                   for p in peaks)

    single = KeyedChunkedStream(monoid, window, shards * slots_per_shard,
                                chunk, horizon=horizon)
    sstate = single.init_state()

    rng = np.random.default_rng((seed, 21))
    z_keys, _, z_xs = (np.asarray(a) for a in KeyedEventStream(
        zipf_rows, keys, zipf_a=zipf_a, seed=seed).in_order())
    all_keys = np.concatenate([rng.permutation(keys).astype(np.int32), z_keys])
    all_xs = np.concatenate([rng.integers(-9, 9, keys).astype(np.int32), z_xs])
    all_ts = np.cumsum(rng.exponential(1.0, all_keys.size)).astype(np.float32)
    n = all_keys.size // chunk * chunk
    mismatched_rows = 0
    t_sharded = t_single = 0.0

    def step_sharded(state, k, x, t):
        t0 = time.perf_counter()
        state, ys, owner = sh.update_chunk(state, k, x, t)
        y = ShardedKeyedStore.collect(ys, owner)
        return state, y, time.perf_counter() - t0

    def step_single(sstate, k, x, t):
        t0 = time.perf_counter()
        sstate, ys, _ = single.process_chunk(sstate, k, x, t)
        return sstate, np.asarray(ys), time.perf_counter() - t0

    for c, lo in enumerate(range(0, n, chunk)):
        k, x, t = (a[lo:lo + chunk] for a in (all_keys, all_xs, all_ts))
        if c == 0:  # both programs compile at once, on two host threads
            with ThreadPoolExecutor(2) as pool:
                fa = pool.submit(step_sharded, state, k, x, t)
                fb = pool.submit(step_single, sstate, k, x, t)
                (state, y, ta), (sstate, y1, tb) = fa.result(), fb.result()
            rec["first_chunk_sharded_s"], rec["first_chunk_single_s"] = ta, tb
        else:
            state, y, ta = step_sharded(state, k, x, t)
            sstate, y1, tb = step_single(sstate, k, x, t)
            t_sharded += ta
            t_single += tb
        mismatched_rows += int(np.sum(y != y1))
    rec["rows"] = n
    rec["sharded_s"], rec["single_s"] = t_sharded, t_single
    rec["mismatched_rows"] = mismatched_rows
    c_sh = jax.device_get(sh.counters(state))
    c_1 = jax.device_get(single.store.counters(sstate))
    rec["sharded_counters"] = {k: int(v) for k, v in c_sh.items()}
    rec["single_counters"] = {k: int(v) for k, v in c_1.items()}
    rec["match"] = mismatched_rows == 0
    clean = all(c["n_dropped"] == 0 and c["n_failed"] == 0
                and c["n_live"] == keys
                for c in (rec["sharded_counters"], rec["single_counters"]))
    rec["ok"] = (rec["match"] and clean and rec["own_device"]
                 and rec["never_whole_stack"])
    return rec


# ---------------------------------------------------------------------------


def phases(seed: int, four_chips: bool, pool) -> list:
    """``(name, thunk)`` per phase.  The keyed steps (~60 s each to compile
    at 2**20 slots on a v5e host) start compiling on ``pool`` now and are
    awaited by the service phases, which run last."""
    if four_chips:
        return [("sharded", lambda: sharded_phase(seed, **SHARDED))]
    monoids = ("sum_i32", "max_i32")
    steps = {m: pool.submit(compile_keyed_step, m, **SERVICE) for m in monoids}
    out = [(f"chunked/{op}", lambda op=op: chunked_phase(op, seed, **CHUNKED))
           for op in ("sum", "max")]
    out += [(f"eventtime/{m}/d={d}",
             lambda m=m, d=d: eventtime_phase(m, d, seed, **EVENTTIME))
            for m in monoids for d in (0, 16)]
    out += [(f"service/{m}",
             lambda m=m: service_phase(m, seed, steps[m].result(), **SERVICE))
            for m in monoids]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase, on four chips")
    args = ap.parse_args(argv)

    import jax

    found = jax.devices()
    if found[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {found}); "
              "nothing was run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(found) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(found)}",
              file=sys.stderr)
        return 2
    devices = found[:need]  # the chips the phases use

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log({"phase": "setup", "jax": jax.__version__, "cache_dir": cache_dir,
         "platform": found[0].platform, "kind": found[0].device_kind,
         "count": len(found), "used": len(devices), "seed": args.seed})

    failed = []
    with ThreadPoolExecutor(2) as pool:
        for name, run in phases(args.seed, args.four_chips, pool):
            t0 = time.perf_counter()
            try:
                rec = run()
            except Exception as e:  # one phase's fault must not hide the rest
                traceback.print_exc()
                rec = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
            rec = {"phase": name, **rec, "seconds": time.perf_counter() - t0,
                   **memory(devices)}
            log(rec)
            if not rec["ok"]:
                failed.append(name)
            gc.collect()

    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    d = found[0]
    log({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind,
                                "count": len(found)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
