#!/usr/bin/env python3
"""Run one benchmark cell of the keyed analytics service on the chip.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process holds the chip: it builds the :class:`AnalyticsService` and
its :class:`ServiceHTTPServer` on their threads.  Traffic comes from
``chipbench/client.py``, a second process that never imports JAX and
posts over HTTP.  Everything is found by name from ``BENCHMARK.json``:
the cell's configuration (``chipbench/configs/<config>.json``), its mix
(``chipbench/traffic/<mix>.json`` and the traffic driver it names) and each
metric (``chipbench/metrics/<metric>.py``).

Set-up (``setup_s``, from process start to the window's opening):
generate the data from ``--seed``, build the service, prefill it through
``ingest()`` in-process and flush, which also compiles or loads every
drain program, warm the query programs, start the server.  The window
lasts ``--seconds``.  With ``--trace 1`` a few seconds in its middle are
recorded by the profiler and the cell's per-layer metrics are printed
instead of its end-to-end ones.

After the window, untimed: flush, then query through ``GET /query`` each
tenant's hottest keys, a seeded sample of the keys the window sent, a few
never-seen keys and the dashboard form, and compare every fold with the
plain reference (:mod:`chipbench.reference`) over the rows that were
acknowledged.  ``correct`` needs every number in ``checks`` at or under
its limit.

The last line of standard output is the result; the lines before it
carry counts that are not metrics.  The last lines of standard error
give each checked number beside its limit.  Without a TPU, or with fewer
chips than the cell asks for, the run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import urllib.parse  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import client, gen, reference, window  # noqa: E402

# what the check after the window queries, per tenant: the hottest keys
# of its last CHECK_RECENT rows, a sample of the window's keys, ids never sent
CHECK_HOT, CHECK_SAMPLE, CHECK_UNSEEN, CHECK_RECENT = 16, 64, 4, 4096
# the check's limits: every comparison is exact
LIMITS = {"mismatched_folds": 0, "missing_rows": 0, "failed_requests": 0,
          "dropped_rows": 0}
EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_cell(root: pathlib.Path, workload: str) -> dict:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its
    configuration, mix and metric specs."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads(
        (root / "chipbench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {"bench": bench, "cell": cell, "config": config, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def read_metrics(root: pathlib.Path, specs: list, rec: dict) -> dict:
    """Each metric's reader, ``chipbench/metrics/<name>.py:read(rec)``; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in specs:
        mod = client.load_module(
            root / "chipbench" / "metrics" / f"{m['name']}.py",
            f"chipbench_metric_{m['name'].replace('.', '_')}")
        v = mod.read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def devices(chips: int, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> None:
    """The program's own placement of JAX's persistent compilation cache
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``),
    holding small programs too, so that a warm run compiles nothing."""
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts backend compilations and persistent-cache loads while
    ``armed``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax._src import monitoring

        self.armed = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.count += 1

    def close(self):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._on)


class GcPauses:
    """The interpreter's garbage-collection pauses while ``armed``:
    ``(generation, seconds)`` each.  A pause stops every thread of the
    server process, the drain and the HTTP handlers alike."""

    def __init__(self):
        self.armed = False
        self.pauses = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.armed and self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def close(self):
        gc.callbacks.remove(self._on)


def host_counters() -> dict:
    """The host's cumulative counters that say where its time went, in
    seconds or counts: CPU time by kind (``/proc/stat``), pressure stall
    time (``/proc/pressure``), the cgroup's CPU throttling, memory events
    (``/proc/vmstat``) and this process's own CPU time, context switches
    and major faults.  A file the host lacks gives nothing."""
    out = {}

    def read(path):
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            return ""

    tick = os.sysconf("SC_CLK_TCK")
    for line in read("/proc/stat").splitlines()[:1]:
        kinds = ("user", "nice", "system", "idle", "iowait", "irq",
                 "softirq", "steal")
        for k, v in zip(kinds, line.split()[1:]):
            out[f"cpu_{k}_s"] = int(v) / tick
    for res in ("cpu", "memory", "io"):
        for line in read(f"/proc/pressure/{res}").splitlines():
            kind, *fields = line.split()
            total = dict(f.split("=") for f in fields).get("total")
            if total is not None:
                out[f"psi_{res}_{kind}_s"] = int(total) / 1e6
    for path, scale in (("/sys/fs/cgroup/cpu.stat", 1e-6),
                        ("/sys/fs/cgroup/cpu/cpu.stat", 1e-9)):
        for line in read(path).splitlines():
            k, v = line.split()
            if k in ("nr_throttled", "throttled_usec", "throttled_time"):
                out[f"cgroup_{k}"] = int(v) * (1 if k == "nr_throttled"
                                               else scale)
    for line in read("/proc/vmstat").splitlines():
        k, v = line.split()
        if k in VMSTAT_EVENTS:
            out[f"vm_{k}"] = int(v)
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out.update(self_user_s=ru.ru_utime, self_system_s=ru.ru_stime,
               self_voluntary_switches=ru.ru_nvcsw,
               self_involuntary_switches=ru.ru_nivcsw,
               self_major_faults=ru.ru_majflt)
    return out


VMSTAT_EVENTS = ("pgmajfault", "pswpin", "pswpout", "allocstall_normal",
                 "allocstall_movable", "pgscan_direct", "compact_stall",
                 "compact_fail", "thp_fault_alloc", "thp_collapse_alloc")


def moved(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b if k in a and b[k] != a[k]}


class HostSampler(threading.Thread):
    """Wakes every ``interval`` seconds and reads :func:`host_counters`.
    A wake-up more than ``late`` seconds behind is a stall, kept with
    what the counters moved across it: a process that ran while its
    sampler could not was held by one of its own threads; one that did
    not run was held from outside."""

    KEEP = 5

    def __init__(self, interval: float = 0.1, late: float = 0.25):
        super().__init__(name="chipbench-host-sampler", daemon=True)
        self.interval, self.late = interval, late
        self.stop_evt = threading.Event()
        self.stalls, self.count, self.worst = [], 0, 0.0
        self.first = self.last = None

    def run(self):
        prev_t, prev = time.monotonic(), host_counters()
        self.first = prev
        while not self.stop_evt.wait(self.interval):
            t, cur = time.monotonic(), host_counters()
            gap = t - prev_t
            if gap > self.interval + self.late:
                self.count += 1
                self.worst = max(self.worst, gap)
                if len(self.stalls) < self.KEEP:
                    self.stalls.append({"at": prev_t, "s": gap,
                                        "moved": moved(prev, cur)})
            prev_t, prev = t, cur
        self.last = host_counters()

    def stop(self) -> None:
        self.stop_evt.set()
        if self.ident is not None:
            self.join(timeout=10)

    def summary(self, t_open: float) -> dict:
        return {"stalls": self.count, "worst_s": self.worst,
                "kept": [dict(st, at=st["at"] - t_open)
                         for st in self.stalls],
                "counters_read": sorted(self.first or {}),
                "window_moved": (moved(self.first, self.last)
                                 if self.first and self.last else {})}


# ---------------------------------------------------------------------------
# the visibility watcher (public counters only)
# ---------------------------------------------------------------------------


class Watcher(threading.Thread):
    """Polls ``stats()`` every ``interval`` seconds and keeps each change
    of the tenants' ``queryable_rows`` with the monotonic time it was
    read."""

    def __init__(self, svc, names: list, interval: float):
        super().__init__(name="chipbench-watcher", daemon=True)
        self.svc, self.names, self.interval = svc, names, interval
        self.stop_evt = threading.Event()
        self.times, self.counts = [], []

    def _poll(self) -> None:
        per = self.svc.stats()["per_tenant"]
        q = tuple(per[n]["queryable_rows"] if n in per else 0
                  for n in self.names)
        if not self.counts or q != self.counts[-1]:
            self.times.append(time.monotonic())
            self.counts.append(q)

    def run(self):
        while not self.stop_evt.is_set():
            self._poll()
            self.stop_evt.wait(self.interval)

    def stop(self):
        """Stop polling, then read once more: the last drain may have
        landed after the last poll."""
        self.stop_evt.set()
        self.join(timeout=10)
        self._poll()

    def timeline(self) -> tuple:
        return (np.asarray(self.times, np.float64),
                np.asarray(self.counts, np.int64).reshape(-1, len(self.names)))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def start_client(root: pathlib.Path, job: dict) -> subprocess.Popen:
    proc = subprocess.Popen(
        [sys.executable, str(root / "chipbench" / "client.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=str(root))
    proc.stdin.write(json.dumps(job) + "\n")
    proc.stdin.flush()
    return proc


def run_cell(root: pathlib.Path, spec: dict, *, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True, poll_s: float = 0.005,
             trace_out: str = None, trace_span: float = None,
             answers=None) -> tuple:
    """One run → ``(result, rec)``.  ``answers(rec)``, if given, replaces
    the folds the service returned after the window (a control);
    ``trace_out`` keeps a copy of the raw profile, of ``trace_span``
    seconds."""
    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    devs = devices(cell["chips"], require_tpu)
    import jax

    sys.path.insert(0, str(root / "src"))
    use_compile_cache()
    from repro.service import AnalyticsService, ServiceConfig, ServiceHTTPServer

    svc_cfg = ServiceConfig(**config["service"])
    g = config["generator"]
    names = [f"tenant-{i}" for i in range(g["tenants"])]
    prefill = mix["prefill_rows_per_tenant"]
    batch = mix["batch_rows"]
    streams = gen.tenant_streams(seed, g)
    job = {"root": str(root), "seed": seed, "generator": g, "mix": mix,
           "tenants": names, "chunk": svc_cfg.chunk, "start_row": prefill,
           "seconds": seconds}
    proc = start_client(root, job)
    compiles = CompileCounter()
    pauses = GcPauses()
    svc = AnalyticsService(svc_cfg).start()
    log("service built")
    srv = watcher = trace_dir = None
    sampler = HostSampler()
    rec = {"seed": seed, "seconds": seconds, "config": config, "mix": mix,
           "cell": cell, "names": names, "chunk": svc_cfg.chunk,
           "device_kind": devs[0].device_kind}
    try:
        # -- set-up: prefill in-process, then warm the query programs -------
        for lo in range(0, prefill, batch):
            for i, name in enumerate(names):
                code, payload, _ = svc.ingest(
                    name, *streams[i].rows(lo, min(lo + batch, prefill)))
                if code != 200:
                    raise RuntimeError(f"prefill refused: {code} {payload}")
        log(f"prefill of {prefill} rows per tenant queued")
        if not svc.flush(timeout=1200):
            raise RuntimeError("prefill did not drain")
        log("prefill drained")
        probe = np.unique(streams[0].rows(0, batch)[0])
        for n in (1, 2, 4, 8, 16):
            svc.query(names[0], keys=probe[:n].tolist())
        for name in names:
            svc.query(name)
        log("query programs warm")
        srv = ServiceHTTPServer(svc).start()
        watcher = Watcher(svc, names, poll_s)
        watcher.start()
        ready = proc.stdout.readline()
        if not ready:
            raise RuntimeError("traffic client failed to start")
        stats0 = svc.stats()
        t_open = time.monotonic() + 0.05
        t_close = t_open + seconds
        rec["setup_s"] = t_open - T_START
        log(f"window opens for {seconds} s")
        proc.stdin.write(json.dumps(
            {"url": srv.url, "t_open": t_open, "t_close": t_close}) + "\n")
        proc.stdin.flush()

        # -- the window -----------------------------------------------------
        compiles.armed = pauses.armed = True
        sampler.start()
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            lead = min(4.0, seconds / 4)
            span = trace_span or min(3.0, seconds / 4)
            client.sleep_until(t_open + lead)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host: runtime events only
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("chipbench.trace_open"):
                rec["trace_open"] = time.monotonic()
            client.sleep_until(rec["trace_open"] + span)
            with jax.profiler.TraceAnnotation("chipbench.trace_close"):
                rec["trace_close"] = time.monotonic()
            jax.profiler.stop_trace()
            if trace_out:
                shutil.copytree(trace_dir, trace_out, dirs_exist_ok=True)
        client.sleep_until(t_close)
        stats1 = svc.stats()
        compiles.armed = pauses.armed = False
        sampler.stop()
        rec["compiles_in_window"] = compiles.count
        rec["gc_pauses"] = pauses.pauses
        rec["host"] = sampler.summary(t_open)
        out, _ = proc.communicate(timeout=seconds + 300)
        if proc.returncode != 0:
            raise RuntimeError(f"traffic client exited {proc.returncode}")
        sent = json.loads(out.strip().splitlines()[-1])

        # -- after the window: flush, read the device, check ----------------
        log("window closed")
        flushed = svc.flush(timeout=300)
        watcher.stop()
        stats2 = svc.stats()
        rec.update(t_open=t_open, t_close=t_close, stats_open=stats0,
                   stats_close=stats1, stats_end=stats2, flushed=flushed,
                   client=sent)
        times, counts = watcher.timeline()
        b = np.asarray(sent["batches"], np.float64).reshape(-1, 8)
        ok = b[:, 4] == 200
        vis = np.full(len(b), np.nan)
        vis[ok] = window.visible_times(times, counts, b[ok, 0].astype(int),
                                b[ok, 5].astype(np.int64))
        rec["batches"] = b
        rec["visible"] = vis
        rec["queries"] = np.asarray(sent["queries"],
                                    np.float64).reshape(-1, 5)
        mem = [d.memory_stats() or {} for d in devs]
        rec["memory_peak_bytes"] = max(
            (m.get("peak_bytes_in_use", 0) for m in mem), default=0)
        acked = [prefill + int(b[ok & (b[:, 0] == i), 6].sum())
                 for i in range(len(names))]
        rec["acked_rows"] = acked
        got, queried = query_check_keys(srv.url, streams, names, acked,
                                        prefill, svc_cfg.key_bits, seed)
        rec["got"], rec["queried"] = got, queried
        if trace:
            from chipbench import trace_reduce

            rec["trace"] = trace_reduce.reduce_dir(trace_dir)
            log("trace reduced")
    finally:
        if watcher is not None and watcher.is_alive():
            watcher.stop()
        sampler.stop()
        if srv is not None:
            srv.stop()
        svc.stop(drain=False)
        if proc.poll() is None:
            proc.kill()
        proc.wait()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        compiles.close()
        pauses.close()
    del svc, srv
    gc.collect()

    # -- the reference, with the program's state freed ----------------------
    log("service stopped; comparing with the reference")
    rec["streams"] = streams
    if answers is not None:
        rec["got"] = answers(rec)
    checks = compare(rec)
    rec["checks"] = checks
    result = make_result(root, spec, rec, devs, trace)
    return result, rec


def log(msg: str) -> None:
    print(f"chipbench {time.monotonic() - T_START:8.2f}s {msg}",
          file=sys.stderr, flush=True)


def query_check_keys(url, streams, names, acked, prefill, key_bits, seed):
    """Query each tenant, keys= form and dashboard form, after the window
    → ``(got, queried)``: ``got[i][key] = (found, fold)`` as replied."""
    got, queried = [], []
    for i, name in enumerate(names):
        keys = streams[i].rows(0, acked[i])[0]
        # the hottest keys of the tenant's last chunk of rows: the auctions
        # its last batches went to
        uniq, counts = np.unique(keys[-CHECK_RECENT:], return_counts=True)
        hot = uniq[np.argsort(-counts, kind="stable")][:CHECK_HOT]
        window_keys = np.setdiff1d(np.unique(keys[prefill:]), hot)
        rng = np.random.default_rng((seed, i, 5))
        picked = rng.choice(window_keys, min(CHECK_SAMPLE, window_keys.size),
                            replace=False) if window_keys.size else []
        unseen = (1 << key_bits) - 1 - np.arange(CHECK_UNSEEN)
        q = np.concatenate([hot, np.sort(picked), unseen]).astype(np.int64)
        path = f"/query?tenant={urllib.parse.quote(name)}"
        answers = {}
        status, doc = client.request(
            url, "GET", path + "&keys=" + ",".join(str(int(k)) for k in q),
            timeout=300.0)
        if status == 200:
            for k, v in doc["keys"].items():
                answers[int(k)] = (v["found"], v["fold"])
        # the dashboard form: the rollup's hot keys, whatever they are
        status, doc = client.request(url, "GET", path, timeout=300.0)
        if status == 200:
            for k, v in doc["keys"].items():
                answers.setdefault(int(k), (v["found"], v["fold"]))
        got.append(answers)
        queried.append(sorted(set(q.tolist()) | set(answers)))
    return got, queried


def compare(rec: dict) -> dict:
    """The checked numbers, each compared with its limit in ``LIMITS``."""
    cfg = rec["config"]["service"]
    mismatched = 0
    for i, stream in enumerate(rec["streams"]):
        k, t, x = stream.rows(0, rec["acked_rows"][i])
        want = reference.key_folds(cfg["monoid"], k, t, x, rec["queried"][i],
                                   window=cfg["window"],
                                   horizon=cfg["horizon"])
        for key in rec["queried"][i]:
            g = rec["got"][i].get(key)
            if g is None or (bool(g[0]), g[1]) != want[key]:
                mismatched += 1
    per = rec["stats_end"]["per_tenant"]
    missing = sum(max(0, a - per[n]["queryable_rows"])
                  for a, n in zip(rec["acked_rows"], rec["names"]))
    if not rec["flushed"]:
        missing = max(missing, 1)
    b, q = rec["batches"], rec["queries"]
    failed = int(np.sum(b[:, 4] != 200)) + int(np.sum(q[:, 4] != 200))
    failed += int(np.sum((b[:, 4] == 200) & np.isnan(rec["visible"])))
    dropped = sum(per[n]["dropped_rows"] + per[n]["shed_rows"]
                  + per[n]["throttled_rows"] for n in rec["names"])
    return {"mismatched_folds": mismatched, "missing_rows": missing,
            "failed_requests": failed, "dropped_rows": dropped}


def make_result(root, spec, rec, devs, trace) -> dict:
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": rec["memory_peak_bytes"]}
    if trace:
        specs = spec["per_layer"]
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
    else:
        specs = spec["end_to_end"]
    checks = rec["checks"]
    result = {
        "correct": all(checks[k] <= LIMITS[k] for k in LIMITS),
        "attempted": int(window.due_in_window(rec["batches"], rec).sum())
        + len(rec["queries"]),
        "failed": checks["failed_requests"],
        "metrics": read_metrics(root, specs, rec),
        "device": device,
    }
    if trace:
        result["breakdown"] = rec["trace"]["breakdown"]
    result["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]}
                        for k in LIMITS}
    return result


def counts_line(rec: dict) -> dict:
    """Counts worth keeping that are not metrics."""
    lat = np.asarray(rec["client"]["lateness_s"], np.float64)
    per = rec["stats_close"]["per_tenant"]
    out = {
        "generator_late_ms": {
            "p50": float(np.percentile(lat, 50) * 1e3) if lat.size else None,
            "p99": float(np.percentile(lat, 99) * 1e3) if lat.size else None,
            "max": float(lat.max() * 1e3) if lat.size else None},
        "backlog_rows_at_close": {n: per[n]["pending_rows"] for n in per},
        "new_key_share": new_key_share(rec),
        "peak_bytes_in_use": rec["memory_peak_bytes"],
        "compiles_in_window": rec["compiles_in_window"],
        "gc_pauses_in_window": {
            "count": len(rec["gc_pauses"]),
            "gen2": sum(1 for g, _ in rec["gc_pauses"] if g == 2),
            "max_ms": max((d for _, d in rec["gc_pauses"]), default=0.0)
            * 1e3},
        "chunks_in_window": (rec["stats_close"]["chunks"]
                             - rec["stats_open"]["chunks"]),
        "stats_reads": rec["client"]["stats_reads"],
        "host": rec["host"],
    }
    return out


def new_key_share(rec: dict) -> dict:
    """Share of rows whose key the tenant had never sent before, over the
    batches that became queryable in the first and the last tenth of the
    window (exact: the slot pool holds every key, nothing is evicted)."""
    b, vis = rec["batches"], rec["visible"]
    t0, t1 = rec["t_open"], rec["t_close"]
    tenth = (t1 - t0) / 10
    new = []
    for s, a in zip(rec["streams"], rec["acked_rows"]):
        _, first = np.unique(s.rows(0, a)[0], return_index=True)
        new.append(np.zeros(a, bool))
        new[-1][first] = True
    out = {}
    for label, lo, hi in (("first_tenth", t0, t0 + tenth),
                          ("last_tenth", t1 - tenth, t1)):
        sel = (vis > lo) & (vis <= hi)
        n_new = sum(int(new[int(r[0])][int(r[7]):int(r[7] + r[6])].sum())
                    for r in b[sel])
        rows = int(b[sel, 6].sum())
        out[label] = n_new / rows if rows else None
    return out


def main(argv=None, *, root: pathlib.Path = ROOT,
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(root, args.workload)
    try:
        result, rec = run_cell(
            root, spec, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), require_tpu=require_tpu)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    print(json.dumps({"counts": counts_line(rec)}), flush=True)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
