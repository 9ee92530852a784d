"""Multi-tenant streaming analytics service over the keyed window engine.

The engines in :mod:`repro.core` are libraries; this module is the front
door that turns them into a service.  Design rules, in order:

* **Never per-event device work.**  HTTP handler threads do numpy
  validation, a token-bucket debit, and a deque append — nothing else.  A
  single consumer thread drains the per-tenant queues in batched
  round-robin: one drained chunk = whole batches of ONE tenant, padded to
  the engine chunk size, fused into ONE
  :meth:`repro.core.keyed.KeyedChunkedStream.process_chunk` dispatch (plus
  one chunk-summary fold and one C=1 rollup observation when rollups are
  on).  I/O is amortized exactly the way the keyed hot path wants.

* **Robustness is load-shedding, not memory.**  Per-tenant token buckets
  throttle over-quota tenants (429 + ``Retry-After``) without touching
  anyone else's tokens; bounded per-tenant queues and a global pending-row
  high-watermark shed bursts (503 + shed accounting) instead of growing
  without bound; and over-capacity chunks degrade gracefully through the
  KeyDirectory's fail-safe drop path, surfaced per tenant (a drained chunk
  is single-tenant, so the store's drop-counter delta attributes cleanly).

* **One engine, namespaced keys.**  Tenant ``idx`` and raw key ``k`` map
  to ``(idx << key_bits) | k`` inside one shared
  :class:`~repro.core.keyed.KeyedChunkedStream` with event-time
  ``horizon=`` windows — per-tenant key spaces are disjoint, so tenant
  isolation is arithmetic, not data structures.  The engine runs
  ``donate=False``: queries read the live state concurrently with drains
  (a pure update returns a fresh state; the swap is one reference
  assignment).

* **Ingest→queryable is measured, not modeled.**  Each accepted batch
  stamps ``perf_counter`` at enqueue; the drain that folds it ends with
  one small host transfer of the store's health counters — a sync point,
  after which the rows are queryable — and records the elapsed time per
  batch (bounded exact ring + optional obs KLL histogram).

Per-tenant rollups ride along as mergeable sketches: each drained chunk is
reduced to ONE product-sketch summary (value-quantile KLL + distinct-key
HLL + heavy-hitter top-k, a log-depth masked fold), and that summary is a
single window element of a :class:`repro.core.telemetry.KeyedTelemetry`
keyed by tenant — ``GET /query`` serves p50/p95/p99, a distinct-key
estimate, and the hottest keys from the last ``rollup_window`` chunks.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.event_time import fold_values
from repro.core.keyed import KeyedChunkedStream
from repro.core.monoids import (
    get_monoid,
    hll_estimate,
    hll_monoid,
    kll_monoid,
    topk_items,
    topk_monoid,
)
from repro.service.config import ServiceConfig
from repro.service.tenancy import Batch, TenantState, TokenBucket, validate_batch


class AnalyticsService:
    """The multi-tenant streaming analytics service (HTTP layer lives in
    :mod:`repro.service.http`; this class is directly drivable in tests).

    Lifecycle::

        svc = AnalyticsService(ServiceConfig()).start()
        status, payload, headers = svc.ingest("tenant-a", keys, ts, values)
        svc.flush()                      # tests/benchmarks: drain the queues
        snap = svc.query("tenant-a", keys=[1, 2, 3])
        svc.stop()
    """

    def __init__(self, cfg: Optional[ServiceConfig] = None):
        self.cfg = cfg = cfg or ServiceConfig()
        self.monoid = get_monoid(cfg.monoid)
        # donate=False: /query reads the live state while the consumer
        # dispatches the next chunk — donation would delete those buffers
        # out from under a concurrent reader (the KeyedTelemetry rule)
        self._engine = KeyedChunkedStream(
            self.monoid, cfg.window, cfg.slots, cfg.chunk,
            horizon=cfg.horizon, donate=False,
        )
        self._state = self._engine.init_state()
        self._query_jit = jax.jit(self._engine.store.query)
        self._prev_health = {k: 0 for k in
                             ("n_evicted", "n_failed", "n_dropped")}

        # per-tenant rollup sketches: the store folds pre-combined CHUNK
        # summaries, so the member monoids carry an identity lift — the
        # heavy per-row lifting happens once per chunk in _summary_jit
        self._rollup = None
        if cfg.rollup:
            from repro.core.telemetry import KeyedTelemetry

            # size the KLL so its weighted capacity k*(2^levels - 1) covers
            # every row the rollup window can hold (rollup_window chunks of
            # cfg.chunk rows): a top-level compaction DROPS its promoted
            # survivors, so an undersized sketch silently sheds mass —
            # cfg.kll_levels is a floor, not the operative value
            need = cfg.rollup_window * cfg.chunk
            levels = cfg.kll_levels
            while cfg.kll_k * ((1 << levels) - 1) < need:
                levels += 1
            self._sketches = {
                "values": kll_monoid(k=cfg.kll_k, levels=levels),
                "distinct": hll_monoid(cfg.hll_registers),
                "hot": topk_monoid(cfg.topk_k),
            }
            self._rollup = KeyedTelemetry(
                {name: dataclasses.replace(m, lift=lambda a: a, lift_batch=None)
                 for name, m in self._sketches.items()},
                cfg.rollup_window,
                slots=cfg.max_tenants,
                chunk=cfg.chunk,
            )
            self._summary_jit = jax.jit(self._chunk_summary)

        # tenancy + accounting (ONE lock; device work never runs under it)
        self._lock = threading.RLock()
        self._tenants: Dict[str, TenantState] = {}
        self._order: List[str] = []     # registration order, for round-robin
        self._rr = 0
        self._pending_rows = 0
        self._chunks = 0
        self._drained_rows = 0
        self._latencies = deque(maxlen=cfg.latency_ring)
        self._t_start = time.monotonic()

        # consumer thread
        self._stop_evt = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._consumer_error: Optional[str] = None

        # obs (attach_obs fills these in)
        self._obs_registry = None
        self._lat_hist = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "AnalyticsService":
        if self._thread is not None:
            raise RuntimeError("service already started")
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._consume, name="service-consumer", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 300.0) -> None:
        """Stop the consumer.  ``drain=True`` first waits for every accepted
        row to become queryable and raises ``TimeoutError`` if it cannot
        (the consumer is stopped either way)."""
        if self._thread is None:
            return
        drained = self.flush(timeout=timeout) if drain else True
        self._stop_evt.set()
        self._wake.set()
        self._thread.join(timeout=timeout)
        self._thread = None
        if not drained:
            raise TimeoutError(
                f"stop(drain=True): {self._pending_rows} accepted rows were "
                f"still pending after {timeout}s"
            )

    def flush(self, timeout: float = 300.0) -> bool:
        """Block until every accepted row is queryable (tests/benchmarks).
        The generous default absorbs first-chunk jit compiles on slow
        hosts; returns False (state possibly still draining) on timeout."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._consumer_error is not None:
                raise RuntimeError(
                    f"service consumer died:\n{self._consumer_error}"
                )
            with self._lock:
                if self._pending_rows == 0:
                    return True
            self._wake.set()
            time.sleep(0.001)
        return False

    def __enter__(self) -> "AnalyticsService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- ingest path (handler threads; host-side only) ---------------------

    def _tenant(self, name: str) -> tuple:
        """Find-or-register under the lock → ``(tenant, error_payload)``."""
        t = self._tenants.get(name)
        if t is not None:
            return t, None
        if len(self._tenants) >= self.cfg.max_tenants:
            return None, {"error": "tenant capacity exhausted",
                          "max_tenants": self.cfg.max_tenants}
        t = TenantState(
            name, len(self._tenants),
            TokenBucket(self.cfg.quota_rows_per_s, self.cfg.quota_burst),
            self.cfg.tenant_queue_batches,
        )
        self._tenants[name] = t
        self._order.append(name)
        return t, None

    def ingest(self, tenant: str, keys, ts, xs) -> tuple:
        """One batch → ``(http_status, payload, headers)``.

        200 accepted · 400 malformed · 413 oversized · 429 over quota
        (``Retry-After`` header) · 503 backpressure or tenant capacity.
        Accounting is all-or-nothing per batch: an accepted batch is
        enqueued whole and will be drained whole.
        """
        cfg = self.cfg
        with self._lock:
            t, err = self._tenant(str(tenant))
            if t is None:
                return 503, err, {}
            last_ts = t.last_ts
        error, payload = validate_batch(
            keys, ts, xs, max_batch=cfg.max_batch, key_limit=cfg.key_limit,
            last_ts=last_ts, value_dtype=cfg.value_dtype,
        )
        if error is not None:
            with self._lock:
                t.rejected_batches += 1
            return error, payload, {}
        k, tsa, x = payload
        n = int(k.shape[0])
        ok, retry_after = t.bucket.try_take(n)
        if not ok:
            with self._lock:
                t.throttled_batches += 1
                t.throttled += n
            return 429, {"error": "quota exhausted",
                         "retry_after": round(retry_after, 3)}, {
                "Retry-After": str(max(1, int(np.ceil(retry_after))))}
        batch = Batch(k, tsa, x, time.perf_counter())
        with self._lock:
            if self._pending_rows + n > cfg.global_rows_hw:
                t.shed += n
                return 503, {"error": "backpressure: global queue "
                                      "high-watermark", "shed": n}, {}
            if len(t.queue) >= t.queue_limit:
                t.shed += n
                return 503, {"error": "backpressure: tenant queue full",
                             "shed": n}, {}
            t.queue.append(batch)
            t.last_ts = float(tsa[-1])
            t.ingested += n
            self._pending_rows += n
            seq = t.ingested
        self._wake.set()
        return 200, {"accepted": n, "seq": seq}, {}

    # -- consumer (the single drain thread) --------------------------------

    def _consume(self) -> None:
        import sys
        import traceback

        while not self._stop_evt.is_set():
            try:
                busy = self._drain_once()
            except Exception:
                # a dead consumer must be LOUD: record the traceback so
                # flush()/ingest() fail fast instead of hanging on queues
                # nobody will ever drain
                self._consumer_error = traceback.format_exc()
                print(f"service consumer died:\n{self._consumer_error}",
                      file=sys.stderr)
                return
            if not busy:
                self._wake.wait(self.cfg.idle_sleep_s)
                self._wake.clear()

    def _pick(self) -> Optional[TenantState]:
        """Round-robin over tenants with pending batches (under the lock)."""
        if not self._order:
            return None
        n = len(self._order)
        for i in range(n):
            t = self._tenants[self._order[(self._rr + i) % n]]
            if t.queue:
                self._rr = (self._rr + i + 1) % n
                return t
        return None

    def _drain_once(self) -> bool:
        cfg = self.cfg
        with self._lock:
            t = self._pick()
            if t is None:
                return False
            # whole batches of ONE tenant, up to the engine chunk
            batches, rows = [], 0
            while t.queue and rows + t.queue[0].n <= cfg.chunk:
                b = t.queue.popleft()
                batches.append(b)
                rows += b.n
        keys = np.concatenate([b.keys for b in batches])
        ts = np.concatenate([b.ts for b in batches])
        xs = np.concatenate([b.xs for b in batches])
        namespaced = (t.idx << cfg.key_bits) | keys.astype(np.int64)
        pk = np.empty(cfg.chunk, np.int32)
        pk[:rows] = namespaced
        pk[rows:] = pk[rows - 1]
        px = np.empty(cfg.chunk, xs.dtype)
        px[:rows] = xs
        px[rows:] = xs[-1]
        mask = np.arange(cfg.chunk) < rows
        pt = None
        if cfg.horizon is not None:
            pt = np.empty(cfg.chunk, np.float32)
            pt[:rows] = ts
            pt[rows:] = ts[-1]
            pt = jnp.asarray(pt)
        # ONE fused engine dispatch for the whole drained chunk
        state, _, _ = self._engine.process_chunk(
            self._state, jnp.asarray(pk), jnp.asarray(px), pt,
            jnp.asarray(mask),
        )
        if self._rollup is not None:
            raw_keys = pk & (cfg.key_limit - 1)  # un-namespace (padded shape)
            summary = self._summary_jit(
                jnp.asarray(raw_keys), jnp.asarray(px), jnp.asarray(mask)
            )
            self._rollup.observe(t.idx, summary)
        # the sync point: one small host transfer of the store's health
        # counters — after this the rows are queryable, and the counter
        # deltas attribute to THIS tenant (single-tenant chunk)
        health = jax.device_get(self._engine.store.counters(state))
        now = time.perf_counter()
        lats = [now - b.t_enqueue for b in batches]
        with self._lock:
            self._state = state
            dropped = int(health["n_dropped"]) - self._prev_health["n_dropped"]
            self._prev_health = {k: int(health[k]) for k in self._prev_health}
            t.dropped += dropped
            t.queryable += rows
            self._pending_rows -= rows
            self._chunks += 1
            self._drained_rows += rows
            self._latencies.extend(lats)
        if self._lat_hist is not None:
            self._lat_hist.observe_many(lats)
        return True

    def _chunk_summary(self, keys, xs, mask):
        """Reduce one drained chunk to a single product-sketch element —
        the rollup store then folds ONE element per chunk instead of C."""
        inputs = {
            "values": xs.astype(jnp.float32),
            "distinct": keys,
            "hot": keys,
        }
        return {
            name: fold_values(m, inputs[name], mask)
            for name, m in self._sketches.items()
        }

    # -- query path --------------------------------------------------------

    def _namespace(self, idx: int, keys: np.ndarray) -> np.ndarray:
        return ((idx << self.cfg.key_bits) | keys.astype(np.int64)).astype(
            np.int32
        )

    def query(self, tenant: str, keys=None, top: int = 10) -> tuple:
        """Tenant snapshot → ``(http_status, payload)``.

        ``keys`` (optional) are raw per-tenant keys to read window folds
        for; defaults to the tenant's hottest keys from the rollup.  The
        payload carries live-key count, rollup sketches (value p50/p95/p99,
        distinct-key estimate, hottest keys), admission counters, and the
        ingest→queryable row lag.
        """
        with self._lock:
            t = self._tenants.get(str(tenant))
            if t is None:
                return 404, {"error": f"unknown tenant {tenant!r}"}
            counters = t.counters()
            idx = t.idx
        state = self._state  # one consistent reference (donate=False)

        rollup = {}
        hot = []
        if self._rollup is not None:
            snap = self._rollup.snapshot(np.asarray([idx], np.int32))
            if bool(snap["found"][0]):
                q50, q95, q99 = np.asarray(snap["values"][0]).tolist()
                rollup["value_quantiles"] = {"p50": q50, "p95": q95, "p99": q99}
                rollup["distinct_keys_est"] = float(
                    hll_estimate(snap["distinct"][0])
                )
                hot = topk_items(
                    jax.tree.map(lambda a: a[0], snap["hot"])
                )[: int(top)]
                rollup["hot_keys"] = [[int(k), int(c)] for k, c in hot]

        if keys is None:
            keys = np.asarray([k for k, _ in hot], np.int64)
        else:
            keys = np.asarray(list(keys), np.int64)
        folds = {}
        if keys.size:
            if keys.min() < 0 or keys.max() >= self.cfg.key_limit:
                return 400, {"error": f"keys must be in [0, {self.cfg.key_limit})"}
            # pow2-pad with the -1 sentinel (never found) so drifting query
            # sizes reuse O(log) compilations — the KeyedTelemetry pattern
            n = int(keys.size)
            cap = 1
            while cap < n:
                cap *= 2
            padded = np.full(cap, -1, np.int32)
            padded[:n] = self._namespace(idx, keys)
            aggs, found = self._query_jit(state, jnp.asarray(padded))
            lowered = jax.device_get(
                {"vals": self.monoid.lower(aggs), "found": found}
            )
            for i, k in enumerate(keys.tolist()):
                folds[str(k)] = {
                    "found": bool(lowered["found"][i]),
                    "fold": np.asarray(lowered["vals"])[i].tolist(),
                }
        # live keys: host scan of the directory for this tenant's namespace
        sk = np.asarray(state["dir"]["slot_key"])
        live = int(np.sum((sk >= 0) & ((sk >> self.cfg.key_bits) == idx)))
        return 200, {
            "tenant": str(tenant),
            "keys": folds,
            "live_keys": live,
            **rollup,
            "counters": counters,
            "lag_rows": counters["pending_rows"],
        }

    def stats(self) -> dict:
        """Service-level snapshot: totals, queue depth, and EXACT
        ingest→queryable latency percentiles over the bounded ring."""
        with self._lock:
            lats = np.asarray(self._latencies, np.float64)
            tenants = {n: t.counters() for n, t in self._tenants.items()}
            out = {
                "tenants": len(tenants),
                "pending_rows": self._pending_rows,
                "chunks": self._chunks,
                "drained_rows": self._drained_rows,
                "uptime_s": round(time.monotonic() - self._t_start, 3),
            }
        lat = {"count": int(lats.size)}
        if lats.size:
            p50, p95, p99 = np.percentile(lats, [50, 95, 99]) * 1e3
            lat.update(p50_ms=round(float(p50), 3),
                       p95_ms=round(float(p95), 3),
                       p99_ms=round(float(p99), 3),
                       max_ms=round(float(lats.max() * 1e3), 3))
        out["ingest_to_queryable"] = lat
        out["per_tenant"] = tenants
        return out

    # -- observability -----------------------------------------------------

    def attach_obs(self, registry=None, *, prefix: str = "repro_service"):
        """Wire the service into a :class:`repro.obs.registry
        .MetricsRegistry`: per-tenant labeled ingested/throttled/shed/
        dropped/lag series, global queue depth and chunk counters, an
        ingest→queryable KLL summary, plus the keyed engine's own store
        health series (``repro_keyed_*``).  Returns the registry (the HTTP
        layer serves ``GET /metrics`` from it)."""
        if registry is None:
            from repro.obs.registry import default_registry

            registry = default_registry()
        self._obs_registry = registry
        self._lat_hist = registry.histogram(
            f"{prefix}_ingest_to_queryable_seconds",
            "ingest accept → rows queryable (per accepted batch)",
        )
        registry.describe(f"{prefix}_pending_rows", "gauge",
                          "rows accepted but not yet queryable (all tenants)")
        registry.describe(f"{prefix}_tenants", "gauge", "registered tenants")
        registry.describe(f"{prefix}_chunks_total", "counter",
                          "fused drain dispatches")
        registry.describe(f"{prefix}_drained_rows_total", "counter",
                          "rows drained into the keyed store")
        per_tenant = {
            "ingested_rows": ("ingested_rows_total", "counter",
                              "rows accepted into the tenant queue"),
            "queryable_rows": ("queryable_rows_total", "counter",
                               "rows drained + synced into the store"),
            "throttled_rows": ("throttled_rows_total", "counter",
                               "rows refused by the tenant quota (429)"),
            "shed_rows": ("shed_rows_total", "counter",
                          "rows refused by backpressure (503)"),
            "dropped_rows": ("dropped_rows_total", "counter",
                             "rows dropped by failed slot admission"),
            "pending_rows": ("lag_rows", "gauge",
                             "ingest→queryable row lag"),
        }
        for _, (suffix, typ, help) in per_tenant.items():
            registry.describe(f"{prefix}_{suffix}", typ, help)

        def collect():
            with self._lock:
                out = {
                    f"{prefix}_pending_rows": self._pending_rows,
                    f"{prefix}_tenants": len(self._tenants),
                    f"{prefix}_chunks_total": self._chunks,
                    f"{prefix}_drained_rows_total": self._drained_rows,
                }
                for name, t in self._tenants.items():
                    c = t.counters()
                    for key, (suffix, _, _) in per_tenant.items():
                        out[f'{prefix}_{suffix}{{tenant="{name}"}}'] = c[key]
            return out

        registry.register_collector(collect)

        # shared-store health straight off the live state (donate=False:
        # the reference a scrape reads stays valid across drains) — the
        # engine's own attach_obs only reports when built with an ObsConfig
        store_series = {
            "n_live": (f"{prefix}_store_live_keys", "gauge",
                       "keys resident in the shared slot pool"),
            "n_evicted": (f"{prefix}_store_evictions_total", "counter",
                          "LRU evictions since init"),
            "n_failed": (f"{prefix}_store_admission_failed_total", "counter",
                         "abandoned slot admissions"),
            "n_dropped": (f"{prefix}_store_dropped_rows_total", "counter",
                          "rows dropped by failed admission"),
        }
        for key, (name, typ, help) in store_series.items():
            registry.describe(name, typ, help)

        def collect_store():
            c = self._engine.store.counters(self._state)
            return {name: c[key] for key, (name, _, _) in store_series.items()}

        registry.register_collector(collect_store)
        return registry
