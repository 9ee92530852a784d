"""The harness end to end on the CPU at a tiny size: it finds cells by
name, refuses to run without a TPU, passes a sound program and fails each
fault the cells can have and the control."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import control, run, window  # noqa: E402

TINY_SERVICE = dict(slots=16384, window=8, chunk=128, max_batch=32,
                    rollup=False)


def tiny_root(dest: pathlib.Path) -> pathlib.Path:
    """A checkout holding the benchmark at a size the CPU runs in
    seconds (rollups off: XLA:CPU compiles their sketch fold for
    minutes)."""
    shutil.copytree(REPO / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dest / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((dest / c["file"]).read_text())
        cfg["service"].update(TINY_SERVICE)
        (dest / c["file"]).write_text(json.dumps(cfg))
    for p in (dest / "chipbench" / "traffic").glob("*.json"):
        mix = json.loads(p.read_text())
        mix.update(prefill_rows_per_tenant=256, batch_rows=32)
        if "rate_events_per_s" in mix:
            mix["rate_events_per_s"] = 1500
        p.write_text(json.dumps(mix))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """One compilation cache for the module's runs: the first compiles,
    the others load."""
    return str(tmp_path_factory.mktemp("jax_cache"))


@pytest.fixture
def cache_env(cache_dir, monkeypatch):
    """The module's cache given as a run on the chip gets one:
    ``$JAX_COMPILATION_CACHE_DIR``, which JAX reads at start-up (here,
    where JAX is already up, its config is set to match)."""
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def test_run_without_tpu_exits_nonzero_with_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "chipbench" / "run.py"), "--workload",
         "bids-max.saturate", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=120)
    assert p.returncode == run.EXIT_NO_CHIP
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_finds_a_config_mix_driver_metric_and_cell_added_as_files(tmp_path):
    root = tiny_root(tmp_path)
    cb = root / "chipbench"
    cfg = json.loads((cb / "configs" / "bids-max.json").read_text())
    cfg["service"]["monoid"] = "sum_i32"
    (cb / "configs" / "new-cfg.json").write_text(json.dumps(cfg))
    (cb / "traffic" / "trickle.py").write_text(
        "def prepare(job, streams):\n    return job\n\n"
        "def run(plan, go):\n    return {'batches': [], 'queries': [], "
        "'lateness_s': [], 'stats_reads': 0}\n")
    (cb / "traffic" / "trickle.json").write_text(json.dumps(
        {"driver": "trickle", "prefill_rows_per_tenant": 64,
         "batch_rows": 32}))
    (cb / "metrics" / "twice_seconds.py").write_text(
        "def read(rec):\n    return 2 * rec['seconds']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "new-cfg", "source": "a test",
                             "file": "chipbench/configs/new-cfg.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "new-cfg.trickle",
                               "config": "new-cfg", "traffic": "trickle",
                               "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "twice_seconds", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "drain loop", "moves": "setup_s",
                               "workloads": ["new-cfg.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = run.load_cell(root, "new-cfg.trickle")
    assert spec["config"]["service"]["monoid"] == "sum_i32"
    assert spec["mix"]["driver"] == "trickle"
    assert [m["name"] for m in spec["end_to_end"]] == ["setup_s"]
    assert [m["name"] for m in spec["per_layer"]] == ["twice_seconds"]
    got = run.read_metrics(root, spec["per_layer"], {"seconds": 3.0})
    assert got == {"twice_seconds": {"value": 6.0, "unit": "s"}}
    from chipbench import byname

    assert byname.driver(root, "trickle").run(None, None)["batches"] == []


def test_finds_a_deployment_with_its_generator_and_fold_by_name(tmp_path):
    """A deployment that is not NEXmark bids, TSBS devops cpu-only under
    a sliding cpu-max-all, goes in as files and entries alone; its rows
    are checked against the reference without starting the service."""
    root = tiny_root(tmp_path)
    cb = root / "chipbench"
    hosts, log_s, readings, horizon = 50, 10, 360, 3600.0
    cfg = json.loads((cb / "configs" / "bids-max.json").read_text())
    cfg["service"].update(monoid="max", value_dtype="f32", window=readings,
                          horizon=horizon)
    cfg["generator"] = {"name": "tsbs_cpu", "tenants": 2, "hosts": hosts,
                        "log_interval_s": log_s}
    (cb / "configs" / "tsbs-cpu-max.json").write_text(json.dumps(cfg))
    (cb / "traffic" / "hour.json").write_text(json.dumps(
        {"driver": "saturate", "prefill_rows_per_tenant": 360 * hosts,
         "batch_rows": 32, "backlog_chunks": [2, 4], "stats_every_s": 0.1}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tsbs-cpu-max", "source": "a test",
                             "file": "chipbench/configs/tsbs-cpu-max.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tsbs-cpu-max.hour",
                               "config": "tsbs-cpu-max", "traffic": "hour",
                               "chips": 1, "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    from chipbench import byname, reference

    spec = run.load_cell(root, "tsbs-cpu-max.hour")
    gen_cfg, svc = spec["config"]["generator"], spec["config"]["service"]
    streams = byname.tenant_streams(root, 2**31 + 77, gen_cfg)
    fold = byname.fold(root, svc["monoid"])
    # an hour and 7 intervals, and 3 hosts of the next: the window of 360
    # readings binds for every host
    acked = [spec["mix"]["prefill_rows_per_tenant"] + 7 * hosts + 3] * 2
    got, queried = [], []
    for s in streams:
        k, t, x = s.rows(0, acked[0])
        assert x.shape == (acked[0], 10) and k.max() == hosts - 1
        answers = {}
        for h in range(hosts):  # the fold, by whole arrays
            mine = np.flatnonzero(k == h)[-readings:]
            mine = mine[t[mine] > t[mine[-1]] - horizon]
            answers[h] = (True, x[mine].max(axis=0).tolist())
        answers[hosts + 3] = (False, [float(fold.IDENTITY)] * 10)
        got.append(answers)
        queried.append(sorted(answers))
    per = {f"tenant-{i}": {"queryable_rows": a, "dropped_rows": 0,
                           "shed_rows": 0, "throttled_rows": 0}
           for i, a in enumerate(acked)}
    rec = {"config": spec["config"], "streams": streams, "fold": fold,
           "acked_rows": acked, "queried": queried, "got": got,
           "names": list(per), "stats_end": {"per_tenant": per},
           "flushed": True, "batches": np.zeros((0, 8)),
           "queries": np.zeros((0, 5)), "visible": np.zeros(0)}
    assert run.compare(rec) == dict.fromkeys(run.LIMITS, 0)
    narrowed = run.compare(dict(rec, got=control.narrow(rec)))
    assert narrowed["mismatched_folds"] == 2 * hosts
    # the generator is named, never defaulted
    with pytest.raises(FileNotFoundError):
        byname.tenant_streams(root, 1, dict(gen_cfg, name="tsbs_cpus"))
    with pytest.raises(KeyError):
        byname.tenant_streams(root, 1, {"tenants": 1, "hosts": 2,
                                        "log_interval_s": 10})


def _plant(monkeypatch, fault: str) -> None:
    """Break the timed path underneath the harness."""
    from repro.core.keyed import KeyedChunkedStream
    from repro.service.core import AnalyticsService

    orig_chunk = KeyedChunkedStream.process_chunk
    orig_query = AnalyticsService.query
    if fault == "state_unchanged":
        def process_chunk(self, state, keys, xs, ts=None, mask=None):
            _, ys, info = orig_chunk(self, state, keys, xs, ts, mask)
            return state, ys, info
        monkeypatch.setattr(KeyedChunkedStream, "process_chunk",
                            process_chunk)
    elif fault == "half_batch":
        def process_chunk(self, state, keys, xs, ts=None, mask=None):
            import jax.numpy as jnp

            # every other row: a chunk of any fill loses half its rows
            half = jnp.arange(keys.shape[0]) % 2 == 0
            return orig_chunk(self, state, keys, xs, ts, mask & half)
        monkeypatch.setattr(KeyedChunkedStream, "process_chunk",
                            process_chunk)
    elif fault == "answer_altered":
        def query(self, tenant, keys=None, top=10):
            code, payload = orig_query(self, tenant, keys, top)
            if code == 200 and payload["keys"]:
                first = next(iter(payload["keys"].values()))
                first["fold"] = first["fold"] + 1
            return code, payload
        monkeypatch.setattr(AnalyticsService, "query", query)


# the bids folds' narrower type is int16, hence the case's name
CONTROLS = {"control_stale": control.stale, "control_int16": control.narrow}


@pytest.mark.parametrize("case", ["sound", "state_unchanged", "half_batch",
                                  "answer_altered", "control_stale",
                                  "control_int16"])
def test_correct_fails_each_fault_and_the_control(case, tmp_path,
                                                  monkeypatch, cache_env):
    root = tiny_root(tmp_path)
    sys.path.insert(0, str(REPO / "src"))
    _plant(monkeypatch, case)
    spec = run.load_cell(root, "bids-max.saturate")
    result, rec = run.run_cell(
        root, spec, seed=2**31 + 12345, seconds=1.0, trace=False,
        require_tpu=False, answers=CONTROLS.get(case))
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert result["correct"] is (case == "sound"), checks
    assert rec["acked_rows"][0] > 256  # the window sent rows
    assert list(result)[-1] == "checks"
    if case == "sound":
        assert checks == dict.fromkeys(run.LIMITS, 0)
        assert np.isfinite(rec["visible"]).all()
        counts = run.counts_line(root, rec)
        # one saturate sender, one POST in flight at a time: its two busy
        # parts fit between the window's opening and its last reply
        b = rec["batches"]
        last_reply = b[window.due_in_window(b, rec), 3].max()
        busy = counts["sender_busy_share"]
        assert busy["due_to_sent"] > 0 and busy["sent_to_replied"] > 0
        assert (busy["due_to_sent"] + busy["sent_to_replied"]) \
            * rec["seconds"] <= last_reply - rec["t_open"]
        assert 0 < counts["chunk_fill"] <= 100.0
        assert counts["chunk_fill"] == run.read_metrics(
            root, [{"name": "chunk_fill", "unit": "%"}],
            rec)["chunk_fill"]["value"]
    else:
        assert checks["mismatched_folds"] > 0
