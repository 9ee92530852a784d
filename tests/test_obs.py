"""Observability layer: registry/scrape/render, zero-overhead gating,
exporter, chrome-trace recorder, KLL accuracy, checkpoint survival.

The contract under test (PR 8):

  * ``ObsConfig(enabled=False)`` is FREE — the traced computation of an
    instrumented engine is byte-identical to an uninstrumented one
    (jaxpr equality), so production can ship the hooks compiled out.
  * One scrape = one ``jax.effects_barrier`` + one batched transfer; the
    Prometheus rendering is well-formed text exposition 0.0.4.
  * Engine counters live INSIDE the engine state pytree, so they ride
    through ``state_dict``/``load_state_dict`` untouched.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import monoids
from repro.core.keyed import KeyedWindowStore
from repro.core.telemetry import KeyedTelemetry
from repro.obs import ObsConfig, default_registry
from repro.obs.registry import KLLHistogram, MetricsRegistry, split_series

rng = np.random.default_rng(0)


def _chunk(C=32, U=8):
    keys = jnp.asarray(rng.integers(0, U, C), jnp.int32)
    xs = jnp.asarray(rng.integers(0, 100, C), jnp.int32)
    return keys, xs


# ---------------------------------------------------------------------------
# Zero-overhead gate: disabled obs must not touch the traced computation
# ---------------------------------------------------------------------------


def test_obs_disabled_jaxpr_byte_identical():
    """An ObsConfig with enabled=False — even with every instrument flag
    raised — must leave update_chunk's jaxpr identical to a store built
    with no obs at all.  This is the 'free when off' guarantee the
    acceptance bench (disabled within 2% of baseline) rests on."""
    m = monoids.sum_monoid(jnp.int32)
    keys, xs = _chunk()
    off = ObsConfig(enabled=False, registry=MetricsRegistry(),
                    instrument_admission=True, instrument_combines=True)
    plain = KeyedWindowStore(m, window=8, slots=16)
    gated = KeyedWindowStore(m, window=8, slots=16, obs=off)
    jx_plain = jax.make_jaxpr(plain.update_chunk)(
        plain.init_state(), keys, xs)
    jx_gated = jax.make_jaxpr(gated.update_chunk)(
        gated.init_state(), keys, xs)
    assert str(jx_plain) == str(jx_gated)


def test_obs_enabled_instrumentation_changes_jaxpr():
    """Sanity for the test above: with enabled=True the admission
    callback IS traced in, so the jaxprs must differ — otherwise the
    equality check proves nothing."""
    m = monoids.sum_monoid(jnp.int32)
    keys, xs = _chunk()
    on = ObsConfig(enabled=True, registry=MetricsRegistry(),
                   instrument_admission=True)
    plain = KeyedWindowStore(m, window=8, slots=16)
    inst = KeyedWindowStore(m, window=8, slots=16, obs=on)
    jx_plain = jax.make_jaxpr(plain.update_chunk)(
        plain.init_state(), keys, xs)
    jx_inst = jax.make_jaxpr(inst.update_chunk)(
        inst.init_state(), keys, xs)
    assert str(jx_plain) != str(jx_inst)


# ---------------------------------------------------------------------------
# Counters ride through checkpoint state
# ---------------------------------------------------------------------------


def test_counters_survive_state_dict_roundtrip():
    """Eviction/drop counters live in the engine state pytree, so a
    checkpoint restore onto a FRESH instance restores them exactly."""
    tel = KeyedTelemetry({"v": monoids.sum_monoid()}, window=4, slots=4)
    # universe 64 ≫ slots 4: forces evictions (and failed admissions once
    # the per-chunk distinct-key count exceeds the directory capacity)
    for _ in range(6):
        keys = rng.integers(0, 64, 32)
        tel.observe_bulk(keys, {"v": jnp.ones(32, jnp.float32)})
    before = tel.counters()
    assert before["n_evicted"] > 0, before

    sd = jax.device_get(tel.state_dict())  # host copy, like a checkpoint
    fresh = KeyedTelemetry({"v": monoids.sum_monoid()}, window=4, slots=4)
    assert fresh.counters()["n_evicted"] == 0
    fresh.load_state_dict(sd)
    assert fresh.counters() == before
    # and the restored instance keeps counting from there
    fresh.observe_bulk(rng.integers(0, 64, 32),
                       {"v": jnp.ones(32, jnp.float32)})
    assert fresh.counters()["n_evicted"] >= before["n_evicted"]


# ---------------------------------------------------------------------------
# Registry: scrape + Prometheus rendering
# ---------------------------------------------------------------------------


def test_registry_scrape_and_render():
    reg = MetricsRegistry()
    reg.gauge("repro_test_gauge", "a gauge").set(3.5)
    # counters are declared by base name; the scrape appends ``_total``
    c = reg.counter("repro_test_ops", "an op counter")
    c.inc()
    c.inc(2)
    h = reg.histogram("repro_test_ms", "latency", quantiles=(0.5, 0.99))
    h.observe_many(np.arange(100.0))
    reg.describe("repro_test_collected", "gauge", "from a collector")
    reg.register_collector(
        lambda: {"repro_test_collected": jnp.float32(7.0)})
    # a RAISING collector must be skipped, not poison the scrape
    # (donated-away state robustness)
    reg.register_collector(lambda: 1 / 0)

    snap = reg.scrape()
    assert snap["repro_test_gauge"] == 3.5
    assert snap["repro_test_ops_total"] == 3.0
    assert snap["repro_test_collected"] == 7.0

    text = reg.render()
    assert "# HELP repro_test_gauge a gauge" in text
    assert "# TYPE repro_test_gauge gauge" in text
    assert "# TYPE repro_test_ops_total counter" in text
    assert "# TYPE repro_test_ms summary" in text
    assert 'repro_test_ms{quantile="0.5"}' in text
    assert "repro_test_ms_count 100" in text
    # every non-comment line is `name{labels} value` with a float value
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, val = line.rsplit(" ", 1)
        float(val)  # must parse
        assert name[0].isalpha() or name[0] == "_", line


def test_split_series_inline_labels():
    assert split_series("repro_x") == ("repro_x", {})
    base, labels = split_series('repro_x{shard="3",zone="a"}')
    assert base == "repro_x"
    assert labels == {"shard": "3", "zone": "a"}


def test_counter_group_scrape_via_default_registry():
    """The module-global admission/combine groups are pre-adopted by the
    default registry and render with branch/engine labels."""
    from repro.obs import counters

    reg = default_registry()
    counters.admission.reset()
    counters.admission.bump("fast", 5)
    snap = reg.scrape()
    assert snap['swag_admission_branch_total{branch="fast"}'] == 5
    assert 'swag_admission_branch_total{branch="fast"} 5' in reg.render()
    counters.admission.reset()


# ---------------------------------------------------------------------------
# KLL sketch accuracy (what /metrics serves as p50/p95/p99)
# ---------------------------------------------------------------------------


def test_kll_quantiles_track_exact_percentiles():
    vals = rng.permutation(np.arange(10_000, dtype=np.float64))
    h = KLLHistogram("t", quantiles=(0.5, 0.95, 0.99))
    # feed in uneven host-side batches; drain() folds them in one dispatch
    for lo in range(0, 10_000, 1337):
        h.observe_many(vals[lo:lo + 1337])
    got = np.asarray(h.quantile_values()).ravel()
    want = np.percentile(vals, [50, 95, 99])
    # KLL at k=64 holds rank error well under 3% of n on this range
    np.testing.assert_allclose(got, want, atol=0.03 * 10_000)
    assert h.count == 10_000


# ---------------------------------------------------------------------------
# Exporter: live /metrics over HTTP
# ---------------------------------------------------------------------------


def test_exporter_serves_prometheus_text():
    from repro.obs.exporter import MetricsExporter

    reg = MetricsRegistry()
    reg.gauge("repro_exported_gauge", "g").set(1.0)
    with MetricsExporter(reg, port=0) as exp:
        body = urllib.request.urlopen(exp.url, timeout=10)
        assert body.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4")
        text = body.read().decode()
        assert "repro_exported_gauge 1" in text
        ok = urllib.request.urlopen(
            f"http://127.0.0.1:{exp.port}/healthz", timeout=10)
        assert ok.read() == b"ok\n"
    # after stop() the port is closed
    with pytest.raises(Exception):
        urllib.request.urlopen(exp.url, timeout=2)


def test_exporter_concurrent_scrapes_while_engine_streams():
    """Two clients hammer /metrics while the engine ingests chunks: every
    response is well-formed, no scrape is lost, and the histogram's drained
    count equals everything observed (the drain pop→fold→assign race would
    silently drop folds here)."""
    import threading

    from repro.core.keyed import KeyedChunkedStream
    from repro.obs.exporter import MetricsExporter

    reg = MetricsRegistry()
    hist = reg.histogram("repro_scrape_race_seconds", "drain-race probe")
    eng = KeyedChunkedStream(
        monoids.sum_monoid(jnp.int32), 16, slots=32, chunk=32, donate=False
    )
    state = eng.init_state()

    def get_state():
        return state

    reg.register_collector(
        lambda: {"repro_live_probe": get_state()["dir"]["n_live"]}
    )

    stop = threading.Event()
    errs: list = []
    bodies: list = []

    def scrape_loop(url):
        try:
            while not stop.is_set():
                # generous timeout: early scrapes pay drain-jit compiles
                with urllib.request.urlopen(url, timeout=120) as r:
                    text = r.read().decode()
                assert "repro_live_probe" in text
                assert "repro_scrape_race_seconds_count" in text
                bodies.append(text)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    n_chunks, per_chunk = 30, 8
    with MetricsExporter(reg, port=0) as exp:
        clients = [threading.Thread(target=scrape_loop, args=(exp.url,))
                   for _ in range(2)]
        for c in clients:
            c.start()
        for i in range(n_chunks):
            keys, xs = _chunk(32)
            state, _, _ = eng.process_chunk(state, keys, xs)
            hist.observe_many(np.full(per_chunk, 0.001 * (i + 1)))
        stop.set()
        for c in clients:
            c.join()
        assert not errs
        assert len(bodies) >= 2  # both clients actually scraped
        # every observation survived the concurrent drains
        import jax

        # weights: level-l items count 2**l — recover the total count
        agg = jax.device_get(hist.aggregate())
        weighted = sum(
            int(n) * (1 << l) for l, n in enumerate(np.asarray(agg["n"]))
        )
        assert weighted == n_chunks * per_chunk
        assert hist.count == n_chunks * per_chunk


def test_histogram_concurrent_drain_loses_nothing():
    """N threads drain while M threads observe: the sketch's weighted item
    count must equal the total observed (regression test for the unlocked
    ``_agg`` read-modify-write)."""
    import threading

    import jax

    h = KLLHistogram("h", k=64, levels=12)
    n_obs_threads, n_drain_threads, per_thread = 4, 4, 250
    start = threading.Barrier(n_obs_threads + n_drain_threads)
    done = threading.Event()

    def observe():
        start.wait()
        for i in range(per_thread):
            h.observe(float(i))

    def drain_loop():
        start.wait()
        while not done.is_set():
            h.drain()

    obs = [threading.Thread(target=observe) for _ in range(n_obs_threads)]
    drains = [threading.Thread(target=drain_loop)
              for _ in range(n_drain_threads)]
    for t in obs + drains:
        t.start()
    for t in obs:
        t.join()
    done.set()
    for t in drains:
        t.join()
    h.drain()
    agg = jax.device_get(h.aggregate())
    weighted = sum(
        int(n) * (1 << l) for l, n in enumerate(np.asarray(agg["n"]))
    )
    assert weighted == n_obs_threads * per_thread
    assert h.count == n_obs_threads * per_thread


# ---------------------------------------------------------------------------
# Chrome trace recorder
# ---------------------------------------------------------------------------


def test_trace_stage_spans_partition_parent(tmp_path):
    from repro.obs.trace import TraceRecorder

    tr = TraceRecorder(process_name="t")
    with tr.span("keyed.chunk", tid=1, args={"chunk": 64}) as args:
        args["rows"] = 64
    stages = {"sort": 600.0, "probe": 250.0, "sweep": 150.0}
    tr.add_stage_spans("keyed.chunk", ts_us=1000.0, dur_us=500.0,
                       stages=stages, tid=1)
    evs = tr.events()
    subs = [e for e in evs if e["name"].startswith("keyed.chunk/")]
    assert len(subs) == 3
    assert abs(sum(e["dur"] for e in subs) - 500.0) < 1.0
    assert abs(sum(e["args"]["roofline_frac"] for e in subs) - 1.0) < 1e-3
    assert all(e["args"]["modeled"] for e in subs)
    # sub-spans tile the parent interval: each starts where the last ended
    subs.sort(key=lambda e: e["ts"])
    for a, b in zip(subs, subs[1:]):
        assert abs((a["ts"] + a["dur"]) - b["ts"]) < 1.0

    path = tmp_path / "trace.json"
    tr.save(str(path))
    doc = json.loads(path.read_text())
    assert doc["traceEvents"]  # Perfetto-loadable envelope
    assert any(e["ph"] == "M" for e in doc["traceEvents"])  # process_name
    assert any(e["ph"] == "X" and e["name"] == "keyed.chunk"
               for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# End-to-end: instrumented keyed engine feeds the registry
# ---------------------------------------------------------------------------


def test_keyed_stream_attach_obs_end_to_end():
    from repro.core.keyed import KeyedChunkedStream

    reg = MetricsRegistry()
    obs = ObsConfig(registry=reg)
    eng = KeyedChunkedStream(monoids.sum_monoid(jnp.int32), window=8,
                             slots=8, chunk=32, obs=obs)
    eng.attach_obs(reg)
    state = eng.init_state()
    for _ in range(3):
        keys, xs = _chunk(C=32, U=32)  # universe ≫ slots → drops/evictions
        state, _, _ = eng.process_chunk(state, keys, xs)
    snap = reg.scrape()
    assert snap["repro_keyed_chunks_total"] == 3
    assert snap["repro_keyed_rows_total"] == 96
    assert snap["repro_keyed_live_keys"] == 8  # slots saturated
    assert snap["repro_keyed_evictions_total"] > 0


def test_roofline_bandwidth_refuses_unknown_backend():
    """The cost models behind the stage spans never borrow the CPU figure
    for a backend (or TPU kind) they have no bandwidth for."""
    from repro.roofline.analysis import (
        BACKEND_EFF_BW,
        effective_bandwidth,
        keyed_update_cost,
    )

    assert effective_bandwidth("cpu") == ("cpu", BACKEND_EFF_BW["cpu"])
    assert keyed_update_cost(1024, 256, backend="cpu")["bw"] == 2.0e9
    for backend in ("gpu", "metal"):
        with pytest.raises(ValueError, match="no effective bandwidth"):
            effective_bandwidth(backend)
        with pytest.raises(ValueError):
            keyed_update_cost(1024, 256, backend=backend)
