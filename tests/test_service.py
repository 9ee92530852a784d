"""Multi-tenant streaming analytics service (PR 10).

The contract under test:

  * **Bit-exact isolation**: a tenant's window folds served by the live
    service equal an offline :class:`repro.core.keyed.KeyedChunkedStream`
    replay of exactly that tenant's accepted rows — regardless of how the
    consumer interleaved other tenants' chunks, and even while a noisy
    neighbor is being throttled.
  * **Admission**: token-bucket quotas 429 with a ``Retry-After`` hint and
    touch nobody else's tokens; queue bounds and the global high-watermark
    503; malformed batches 400/413 without side effects on the engine.
  * **HTTP surface**: POST /ingest and GET /query,/stats,/healthz,/metrics
    over stdlib urllib against a live ephemeral-port server.
  * **Observability**: per-tenant labeled series appear in the Prometheus
    exposition and agree with the service's own counters.
"""

import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.keyed import KeyedChunkedStream
from repro.core.monoids import get_monoid
from repro.service import (
    AnalyticsService,
    ServiceConfig,
    ServiceHTTPServer,
    TokenBucket,
    validate_batch,
)

rng = np.random.default_rng(7)

CFG = dict(window=32, horizon=4.0, slots=128, chunk=128, max_batch=64,
           quota_rows_per_s=1e9, quota_burst=1e9, rollup=True,
           rollup_window=8, kll_k=16, kll_levels=4, hll_registers=16,
           topk_k=4, latency_ring=1024)


def _batches(n_batches, n=48, keys_hi=20, seed=0, t0=0.0):
    """Deterministic valid batches: non-decreasing ts across the list."""
    r = np.random.default_rng(seed)
    t = t0
    out = []
    for _ in range(n_batches):
        keys = r.integers(0, keys_hi, n)
        ts = np.sort(t + r.random(n) * 0.5)
        t = float(ts[-1])
        xs = r.integers(0, 100, n)
        out.append((keys, ts, xs))
    return out


def _offline_folds(cfg: ServiceConfig, batches, query_keys):
    """Oracle: replay accepted rows through a fresh KeyedChunkedStream
    (raw keys, same window/horizon) and query the same keys."""
    eng = KeyedChunkedStream(
        get_monoid(cfg.monoid), cfg.window, cfg.slots, cfg.chunk,
        horizon=cfg.horizon, donate=False,
    )
    state = eng.init_state()
    keys = np.concatenate([b[0] for b in batches]).astype(np.int32)
    ts = np.concatenate([b[1] for b in batches]).astype(np.float32)
    xs = np.concatenate([b[2] for b in batches]).astype(np.int32)
    state, _ = eng.stream(keys, xs, ts=ts, state=state)
    aggs, found = eng.query(state, jnp.asarray(query_keys, jnp.int32))
    return (np.asarray(eng.monoid.lower(aggs)), np.asarray(found))


# ---------------------------------------------------------------------------
# Admission primitives
# ---------------------------------------------------------------------------


def test_token_bucket_refill_and_retry_after():
    now = [0.0]
    b = TokenBucket(rate=10.0, burst=20.0, clock=lambda: now[0])
    ok, _ = b.try_take(20)
    assert ok
    ok, retry = b.try_take(5)
    assert not ok and retry == pytest.approx(0.5)
    now[0] += 0.5  # 5 tokens accrue
    ok, _ = b.try_take(5)
    assert ok
    assert b.tokens == pytest.approx(0.0)


def test_validate_batch_rejections():
    common = dict(max_batch=8, key_limit=16, last_ts=-np.inf,
                  value_dtype="i32")
    ok = lambda *a, **kw: validate_batch(*a, **{**common, **kw})
    assert ok([1], [0.0], [5])[0] is None
    assert ok([], [], [])[0] == 400                      # empty
    assert ok([1, 2], [0.0], [5, 6])[0] == 400           # ragged
    assert ok(list(range(9)), [0.0] * 9, [0] * 9)[0] == 413
    assert ok([16], [0.0], [1])[0] == 400                # key out of range
    assert ok([-1], [0.0], [1])[0] == 400
    assert ok([1], [np.inf], [1])[0] == 400              # non-finite ts
    assert ok([1, 2], [2.0, 1.0], [0, 0])[0] == 400      # decreasing ts
    assert ok([1], [0.5], [1], last_ts=1.0)[0] == 400    # behind watermark
    err, payload = ok([3], [1.5], [7])
    assert err is None
    k, t, x = payload
    assert k.dtype == np.int32 and t.dtype == np.float32
    assert x.dtype == np.int32


def test_config_validation():
    with pytest.raises(ValueError):
        ServiceConfig(key_bits=28, max_tenants=64)   # int32 overflow
    with pytest.raises(ValueError):
        ServiceConfig(max_batch=2048, chunk=1024)    # batch > chunk
    with pytest.raises(ValueError):
        ServiceConfig(value_dtype="f64")
    assert ServiceConfig(key_bits=20).key_limit == 1 << 20


# ---------------------------------------------------------------------------
# In-process service: correctness and isolation
# ---------------------------------------------------------------------------


def test_service_folds_match_offline_replay():
    """The tentpole bit-exactness claim, two interleaved tenants."""
    cfg = ServiceConfig(**CFG)
    qk = list(range(20))
    with AnalyticsService(cfg) as svc:
        ba = _batches(6, seed=1)
        bb = _batches(6, seed=2)
        for (ka, ta, xa), (kb, tb, xb) in zip(ba, bb):
            assert svc.ingest("a", ka, ta, xa)[0] == 200
            assert svc.ingest("b", kb, tb, xb)[0] == 200
        assert svc.flush()
        for name, batches in (("a", ba), ("b", bb)):
            code, snap = svc.query(name, keys=qk)
            assert code == 200
            vals, found = _offline_folds(cfg, batches, qk)
            for i, k in enumerate(qk):
                assert snap["keys"][str(k)]["found"] == bool(found[i])
                assert snap["keys"][str(k)]["fold"] == int(vals[i]), (name, k)


def test_quota_throttles_one_tenant_not_the_other():
    """Noisy neighbor 429s; the in-quota tenant's folds stay bit-exact."""
    # every tenant gets the same bucket (rate 1 row/s, burst 150 rows):
    # "good" stays inside the burst (3×48=144 rows), "noisy" blows through
    # it (5×48=240 rows → first 3 batches accepted, then 429s)
    cfg = ServiceConfig(**{**CFG, "quota_rows_per_s": 1.0,
                           "quota_burst": 150.0})
    qk = list(range(20))
    with AnalyticsService(cfg) as svc:
        good = _batches(3, n=48, seed=3)
        noisy = _batches(5, n=48, seed=4)
        codes = []
        for i, (kn, tn, xn) in enumerate(noisy):
            codes.append(svc.ingest("noisy", kn, tn, xn)[0])
            if i < len(good):
                assert svc.ingest("good", *good[i])[0] == 200
        assert codes.count(200) == 3 and codes.count(429) == 2
        assert svc.flush()
        _, snap_n = svc.query("noisy")
        assert snap_n["counters"]["throttled_rows"] == 2 * 48
        assert snap_n["counters"]["throttled_batches"] == 2
        # the good tenant never throttled, and its outputs are the offline
        # replay of its accepted rows — unaffected by the neighbor's 429s
        code, snap = svc.query("good", keys=qk)
        assert snap["counters"]["throttled_rows"] == 0
        assert snap["counters"]["ingested_rows"] == 3 * 48
        vals, found = _offline_folds(cfg, good, qk)
        for i, k in enumerate(qk):
            assert snap["keys"][str(k)]["found"] == bool(found[i])
            assert snap["keys"][str(k)]["fold"] == int(vals[i])


def test_retry_after_header_and_recovery():
    cfg = ServiceConfig(**{**CFG, "quota_rows_per_s": 1000.0,
                           "quota_burst": 10.0})
    with AnalyticsService(cfg) as svc:
        k, t, x = np.asarray([1] * 10), np.linspace(0, 1, 10), np.ones(10)
        assert svc.ingest("a", k, t, x)[0] == 200
        code, payload, hdrs = svc.ingest("a", k, t + 2, x)
        assert code == 429
        assert float(hdrs["Retry-After"]) >= 0
        assert payload["retry_after"] > 0


def test_backpressure_sheds_when_consumer_stalled():
    """With the consumer not running, bounded queues must 503, not grow."""
    cfg = ServiceConfig(**{**CFG, "tenant_queue_batches": 2,
                           "global_rows_hw": 10_000})
    svc = AnalyticsService(cfg)  # .start() never called: queues only fill
    batches = _batches(4, n=16, seed=5)
    codes = [svc.ingest("a", *b)[0] for b in batches]
    assert codes == [200, 200, 503, 503]
    assert svc._tenants["a"].shed == 2 * 16
    # global high-watermark trips even with queue room
    cfg2 = ServiceConfig(**{**CFG, "tenant_queue_batches": 100,
                            "global_rows_hw": 40})
    svc2 = AnalyticsService(cfg2)
    codes = [svc2.ingest("a", *b)[0] for b in _batches(4, n=16, seed=6)]
    assert codes == [200, 200, 503, 503]


def test_stop_raises_when_drain_times_out():
    """stop(drain=True) must not report a drain that never finished."""
    cfg = ServiceConfig(**CFG)
    svc = AnalyticsService(cfg)
    svc._drain_once = lambda: False  # a consumer that never drains
    svc.start()
    assert svc.ingest("a", *_batches(1, n=16, seed=8)[0])[0] == 200
    assert not svc.flush(timeout=0.05)
    with pytest.raises(TimeoutError, match="still pending"):
        svc.stop(drain=True, timeout=0.05)
    assert svc._thread is None  # the consumer is stopped either way
    svc.stop()  # idempotent once stopped


def test_malformed_and_unknown():
    cfg = ServiceConfig(**CFG)
    with AnalyticsService(cfg) as svc:
        code, payload, _ = svc.ingest("a", [1, 2], [0.0], [1])
        assert code == 400
        code, _, _ = svc.ingest("a", [1], [1.0], [1])
        assert code == 200
        code, payload, _ = svc.ingest("a", [1], [0.5], [1])  # behind watermark
        assert code == 400
        assert svc.query("nope")[0] == 404
        svc.flush()
        assert svc.query("a", keys=[1 << 25])[0] == 400  # out of key space


def test_tenant_capacity():
    cfg = ServiceConfig(**{**CFG, "max_tenants": 2})
    with AnalyticsService(cfg) as svc:
        assert svc.ingest("a", [1], [0.0], [1])[0] == 200
        assert svc.ingest("b", [1], [0.0], [1])[0] == 200
        assert svc.ingest("c", [1], [0.0], [1])[0] == 503


def test_rollup_sketches_in_query():
    cfg = ServiceConfig(**CFG)
    with AnalyticsService(cfg) as svc:
        r = np.random.default_rng(0)
        # heavy key 3: half of all rows
        keys = np.where(r.random(64 * 4) < 0.5, 3, r.integers(0, 16, 64 * 4))
        ts = np.sort(r.random(64 * 4))
        xs = np.full(64 * 4, 7)
        for i in range(4):
            sl = slice(64 * i, 64 * (i + 1))
            assert svc.ingest("a", keys[sl], ts[sl], xs[sl])[0] == 200
        assert svc.flush()
        _, snap = svc.query("a", top=3)
        assert snap["hot_keys"][0][0] == 3           # heavy hitter surfaced
        assert snap["value_quantiles"]["p50"] == 7.0  # constant values
        assert 4 <= snap["distinct_keys_est"] <= 64   # coarse 16-reg sketch
        assert snap["live_keys"] >= 10
        # default key set = hottest keys
        assert str(3) in snap["keys"]


def test_stats_latency_percentiles():
    cfg = ServiceConfig(**CFG)
    with AnalyticsService(cfg) as svc:
        for b in _batches(3, seed=8):
            assert svc.ingest("a", *b)[0] == 200
        assert svc.flush()
        s = svc.stats()
        assert s["drained_rows"] == 3 * 48
        lat = s["ingest_to_queryable"]
        assert lat["count"] == 3
        assert 0 < lat["p50_ms"] <= lat["p99_ms"] <= lat["max_ms"]


# ---------------------------------------------------------------------------
# HTTP surface (live ephemeral-port server, stdlib client)
# ---------------------------------------------------------------------------


def _post(url, doc):
    req = urllib.request.Request(
        url, json.dumps(doc).encode(), {"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_http_end_to_end():
    cfg = ServiceConfig(**CFG)
    svc = AnalyticsService(cfg)
    svc.attach_obs(__import__("repro.obs.registry", fromlist=["x"])
                   .MetricsRegistry())
    with ServiceHTTPServer(svc) as srv:
        assert _get(f"{srv.url}/healthz")[1] == "ok\n"
        batches = _batches(4, seed=9)
        for k, t, x in batches:
            code, payload, _ = _post(f"{srv.url}/ingest", {
                "tenant": "web", "keys": k.tolist(), "ts": t.tolist(),
                "values": x.tolist(),
            })
            assert code == 200 and payload["accepted"] == 48
        assert svc.flush()
        code, body = _get(f"{srv.url}/query?tenant=web&keys=0,1,2&top=4")
        assert code == 200
        snap = json.loads(body)
        vals, found = _offline_folds(cfg, batches, [0, 1, 2])
        for i, k in enumerate([0, 1, 2]):
            assert snap["keys"][str(k)]["fold"] == int(vals[i])
        assert len(snap["hot_keys"]) <= 4
        # stats + malformed + unknown routes
        stats = json.loads(_get(f"{srv.url}/stats")[1])
        assert stats["per_tenant"]["web"]["ingested_rows"] == 4 * 48
        assert _post(f"{srv.url}/ingest", {"tenant": "web"})[0] == 400
        assert _get(f"{srv.url}/nope")[0] == 404
        assert _get(f"{srv.url}/query")[0] == 400
        # /metrics carries per-tenant labeled series matching counters
        code, text = _get(f"{srv.url}/metrics")
        assert code == 200
        line = [l for l in text.splitlines()
                if l.startswith('repro_service_ingested_rows_total{tenant="web"}')]
        assert line and float(line[0].split()[-1]) == 4 * 48
        assert "repro_service_ingest_to_queryable_seconds" in text
        line = [l for l in text.splitlines()
                if l.startswith("repro_service_store_live_keys ")]
        assert line and float(line[0].split()[-1]) > 0  # store health rides along
    assert svc._thread is None  # server owned the service lifecycle


def test_http_429_surfaces_retry_after():
    cfg = ServiceConfig(**{**CFG, "quota_rows_per_s": 1.0,
                           "quota_burst": 20.0})
    svc = AnalyticsService(cfg)
    with ServiceHTTPServer(svc) as srv:
        doc = {"tenant": "t", "keys": [1] * 16,
               "ts": list(np.linspace(0, 1, 16)), "values": [1] * 16}
        assert _post(f"{srv.url}/ingest", doc)[0] == 200
        doc["ts"] = list(np.linspace(2, 3, 16))
        code, payload, hdrs = _post(f"{srv.url}/ingest", doc)
        assert code == 429
        assert int(hdrs["Retry-After"]) >= 1


def test_http_concurrent_ingest_two_tenants():
    """Parallel handler threads → consistent accounting, no lost rows."""
    cfg = ServiceConfig(**CFG)
    with AnalyticsService(cfg) as svc, ServiceHTTPServer(svc) as srv:
        errs = []

        def pump(tenant, seed):
            try:
                for k, t, x in _batches(6, n=32, seed=seed):
                    code, _, _ = _post(f"{srv.url}/ingest", {
                        "tenant": tenant, "keys": k.tolist(),
                        "ts": t.tolist(), "values": x.tolist(),
                    })
                    assert code == 200
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=pump, args=(f"t{i}", 10 + i))
                   for i in range(3)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errs
        assert svc.flush()
        stats = svc.stats()
        assert stats["drained_rows"] == 3 * 6 * 32
        for i in range(3):
            assert stats["per_tenant"][f"t{i}"]["queryable_rows"] == 6 * 32
