"""Metrics registry: counters, gauges, KLL histograms, batched scrape.

The observability spine every engine reports into.  Three ingestion paths,
chosen so that NOTHING here ever adds a per-event device callback to a hot
path (the PR-2 telemetry lesson — per-event host roundtrips were 100×):

  * **host counters / gauges** (:class:`repro.obs.counters.CounterGroup`,
    :class:`Gauge`) — plain Python values, bumped from host driver code or
    from the engines' existing ``jax.debug.callback`` instrumentation;
  * **KLL histograms** (:class:`KLLHistogram`) — ``observe()`` appends to a
    host-side buffer (no dispatch); the buffered values are folded into the
    fixed-shape mergeable sketch of :func:`repro.core.monoids.kll_monoid`
    in ONE jitted dispatch at scrape time (or when the buffer fills);
  * **collectors** — callables registered by the engines that return a
    ``{series_name: value}`` dict of *device or host* scalars pulled
    straight from engine state.  The registry gathers every collector's
    tree and host-transfers it in ONE ``jax.device_get`` per scrape.

:meth:`MetricsRegistry.scrape` is therefore: one ``jax.effects_barrier()``
(flushing the counter-group debug callbacks — the discipline lives in
:mod:`repro.obs.counters`), one histogram drain, one batched device
transfer.  Engines in steady state pay nothing beyond the instrumentation
they were explicitly built with.

Series names follow Prometheus conventions (``repro_<engine>_<what>``,
``_total`` suffix for counters); a collector may attach labels inline:
``repro_keyed_shard_dropped_total{shard="2"}``.

:class:`ObsConfig` is the single gate engines take: ``enabled=False`` (or
``obs=None``) must leave the engine's traced computation byte-identical to
an uninstrumented build — the overhead tests assert jaxpr equality.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.obs.counters import CounterGroup

PyTree = Any

_QUANTILES = (0.5, 0.95, 0.99)


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _format_value(v: float) -> str:
    f = float(v)
    if np.isnan(f):
        return "NaN"
    if np.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    return repr(f)


def split_series(name: str) -> Tuple[str, Dict[str, str]]:
    """``'foo{a="1",b="x"}'`` → ``('foo', {'a': '1', 'b': 'x'})``."""
    if "{" not in name:
        return name, {}
    base, rest = name.split("{", 1)
    rest = rest.rstrip("}")
    labels: Dict[str, str] = {}
    for part in rest.split(","):
        if not part:
            continue
        k, v = part.split("=", 1)
        labels[k.strip()] = v.strip().strip('"')
    return base, labels


class Gauge:
    """A host-set gauge family; ``set()`` with optional labels."""

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._vals: Dict[Tuple[Tuple[str, str], ...], float] = {}

    def set(self, value: float, labels: Optional[Dict[str, str]] = None) -> None:
        key = tuple(sorted((labels or {}).items()))
        self._vals[key] = float(value)

    def samples(self) -> List[Tuple[Dict[str, str], float]]:
        return [(dict(k), v) for k, v in self._vals.items()]


class HostCounter:
    """A host-bumped monotone counter family (no labels)."""

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n


class KLLHistogram:
    """Latency/size distribution as a mergeable KLL sketch.

    ``observe(x)`` is host-append only; the buffer is folded into the
    fixed-shape sketch (:func:`repro.core.monoids.kll_monoid`) in one
    jitted dispatch per drain — padded to power-of-two lengths so a drifting
    buffer size reuses O(log) compilations.  Rendered as a Prometheus
    ``summary`` (quantile-labelled gauges + ``_count`` / ``_sum``).
    """

    def __init__(
        self,
        name: str,
        help: str = "",
        *,
        k: int = 64,
        levels: int = 8,
        quantiles: Tuple[float, ...] = _QUANTILES,
    ):
        from repro.core.monoids import kll_monoid

        self.name = name
        self.help = help
        self.quantiles = tuple(quantiles)
        self._m = kll_monoid(k=k, levels=levels, quantiles=self.quantiles)
        self._agg = self._m.identity()
        self._buf: List[float] = []
        self._lock = threading.Lock()
        self._drain_lock = threading.Lock()
        self.count = 0
        self.sum = 0.0
        self._drain_jits: Dict[int, Callable] = {}

    def observe(self, value: float) -> None:
        with self._lock:
            self._buf.append(float(value))
            self.count += 1
            self.sum += float(value)

    def observe_many(self, values) -> None:
        arr = np.asarray(values, np.float64).ravel()
        with self._lock:
            self._buf.extend(arr.tolist())
            self.count += arr.size
            self.sum += float(arr.sum())

    def _drain_fn(self, n: int) -> Callable:
        fn = self._drain_jits.get(n)
        if fn is None:
            import jax

            from repro.core.event_time import fold_values

            m = self._m

            def drain(agg, vals, mask):
                return m.combine(agg, fold_values(m, vals, mask))

            fn = self._drain_jits[n] = jax.jit(drain)
        return fn

    def drain(self) -> None:
        """Fold the pending buffer into the sketch: ONE jitted dispatch.

        ``_drain_lock`` serializes the whole pop→fold→assign sequence:
        two concurrent scrapes would otherwise pop disjoint buffers but
        race the unlocked ``_agg`` read-modify-write, silently losing one
        fold.  ``observe()`` only ever takes the buffer lock, so the hot
        path never waits on a device dispatch."""
        with self._drain_lock:
            with self._lock:
                buf, self._buf = self._buf, []
            if not buf:
                return
            import jax.numpy as jnp

            n = 1
            while n < len(buf):
                n *= 2
            vals = np.zeros(n, np.float32)
            vals[: len(buf)] = buf
            mask = np.arange(n) < len(buf)
            self._agg = self._drain_fn(n)(
                self._agg, jnp.asarray(vals), jnp.asarray(mask)
            )

    def quantile_values(self):
        """Device array of the configured quantiles (drains first)."""
        from repro.core.monoids import kll_quantiles

        self.drain()
        return kll_quantiles(self._agg, self.quantiles)

    def aggregate(self) -> PyTree:
        """The raw mergeable sketch Agg (drains first) — checkpoint or
        cross-process merge payload."""
        self.drain()
        return self._agg


@dataclasses.dataclass
class ObsConfig:
    """The one gate engines consult before instrumenting anything.

    ``enabled=False`` — or passing ``obs=None`` — must leave the engine's
    traced computation byte-identical to an uninstrumented build: no debug
    callbacks, no extra outputs, donation intact.  The flags below opt into
    the jit-visible instrumentation the engines already support (admission
    branch callbacks; combine counting, which forces the lax sweep path) —
    they only take effect while ``enabled``.
    """

    enabled: bool = True
    registry: Optional["MetricsRegistry"] = None
    trace: Optional[Any] = None  # a repro.obs.trace.TraceRecorder
    instrument_admission: bool = False
    instrument_combines: bool = False

    @property
    def active(self) -> bool:
        return self.enabled

    def resolved_registry(self) -> "MetricsRegistry":
        return self.registry if self.registry is not None else default_registry()

    def admission_flag(self) -> bool:
        return self.enabled and self.instrument_admission

    def combines_flag(self) -> bool:
        return self.enabled and self.instrument_combines


class MetricsRegistry:
    """Registry + scrape: every metric family this process exposes.

    ``scrape()`` returns ``{series_name: float}`` after one effects
    barrier, one histogram drain per registered histogram, and ONE batched
    ``jax.device_get`` over every collector's pulled state.  ``render()``
    emits Prometheus text exposition format 0.0.4.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counter_groups: List[CounterGroup] = []
        self._gauges: Dict[str, Gauge] = {}
        self._counters: Dict[str, HostCounter] = {}
        self._histograms: Dict[str, KLLHistogram] = {}
        self._collectors: List[Callable[[], Dict[str, Any]]] = []
        self._descriptions: Dict[str, Tuple[str, str]] = {}  # name -> (type, help)

    # -- registration ------------------------------------------------------

    def gauge(self, name: str, help: str = "") -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name, help)
            return g

    def counter(self, name: str, help: str = "") -> HostCounter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = HostCounter(name, help)
            return c

    def histogram(self, name: str, help: str = "", **kll_kwargs) -> KLLHistogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = KLLHistogram(name, help, **kll_kwargs)
            return h

    def counter_group(self, group: CounterGroup) -> CounterGroup:
        """Adopt a :class:`repro.obs.counters.CounterGroup` (e.g. the
        admission/combine groups) into this registry's exposition."""
        with self._lock:
            if group not in self._counter_groups:
                self._counter_groups.append(group)
        return group

    def register_collector(self, fn: Callable[[], Dict[str, Any]]) -> None:
        """``fn()`` → ``{series_name: scalar}`` pulled at every scrape.
        Values may be live device arrays — the registry batches the host
        transfer.  Series names may carry inline labels
        (``name{shard="0"}``).  A collector that raises is skipped for that
        scrape (e.g. its engine state was donated away mid-flight)."""
        with self._lock:
            self._collectors.append(fn)

    def describe(self, name: str, type: str = "gauge", help: str = "") -> None:
        """Pre-declare TYPE/HELP for collector-produced series."""
        self._descriptions[name] = (type, help)

    # -- scrape ------------------------------------------------------------

    def scrape(self) -> Dict[str, float]:
        """Flat ``{series: value}`` snapshot — ONE effects barrier (via the
        counter groups), ONE batched device transfer for collectors."""
        import jax

        jax.effects_barrier()  # flush debug-callback counter bumps
        out: Dict[str, float] = {}
        with self._lock:
            groups = list(self._counter_groups)
            gauges = list(self._gauges.values())
            counters = list(self._counters.values())
            hists = list(self._histograms.values())
            collectors = list(self._collectors)
        for g in groups:
            for k, v in g._vals.items():
                out[f'{g.name}_total{{{g.label}="{_escape_label(k)}"}}'] = float(v)
        for c in counters:
            out[f"{c.name}_total"] = float(c.value)
        for g in gauges:
            for labels, v in g.samples():
                if labels:
                    lab = ",".join(
                        f'{k}="{_escape_label(str(vv))}"'
                        for k, vv in sorted(labels.items())
                    )
                    out[f"{g.name}{{{lab}}}"] = v
                else:
                    out[g.name] = v
        # collectors: pull every tree, transfer once; a failing collector
        # (donated-away state, torn-down engine) is skipped this scrape
        pulled: List[Dict[str, Any]] = []
        for fn in collectors:
            try:
                pulled.append(dict(fn()))
            except Exception:
                continue
        try:
            pulled = jax.device_get(pulled)
        except Exception:
            safe = []
            for d in pulled:
                try:
                    safe.append(jax.device_get(d))
                except Exception:
                    continue
            pulled = safe
        for d in pulled:
            for name, v in d.items():
                out[name] = float(np.asarray(v))
        # histograms last: drain (one dispatch each) then batch the
        # quantile transfers
        qvals = [h.quantile_values() for h in hists]
        qvals = jax.device_get(qvals)
        for h, qs in zip(hists, qvals):
            for q, v in zip(h.quantiles, np.asarray(qs).ravel()):
                out[f'{h.name}{{quantile="{q:g}"}}'] = float(v)
            out[f"{h.name}_count"] = float(h.count)
            out[f"{h.name}_sum"] = float(h.sum)
        return out

    # -- exposition --------------------------------------------------------

    def _family_meta(self, base: str) -> Tuple[str, str]:
        if base in self._descriptions:
            return self._descriptions[base]
        for g in self._counter_groups:
            if base == f"{g.name}_total":
                return "counter", g.help
        for name, c in self._counters.items():
            if base == f"{name}_total":
                return "counter", c.help
        if base in self._gauges:
            return "gauge", self._gauges[base].help
        for name, h in self._histograms.items():
            if base in (name, f"{name}_count", f"{name}_sum"):
                return "summary", h.help
        if base.endswith("_total"):
            return "counter", ""
        return "gauge", ""

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        samples = self.scrape()
        by_family: Dict[str, List[Tuple[str, float]]] = {}
        for series, value in samples.items():
            base, _ = split_series(series)
            # summary sub-series group under the histogram family name
            for h in self._histograms.values():
                if base in (f"{h.name}_count", f"{h.name}_sum"):
                    base = h.name
                    break
            by_family.setdefault(base, []).append((series, value))
        lines: List[str] = []
        for base in sorted(by_family):
            typ, help = self._family_meta(base)
            if help:
                lines.append(f"# HELP {base} {_escape_help(help)}")
            lines.append(f"# TYPE {base} {typ}")
            for series, value in sorted(by_family[base]):
                lines.append(f"{series} {_format_value(value)}")
        return "\n".join(lines) + "\n"


_DEFAULT: Optional[MetricsRegistry] = None
_DEFAULT_LOCK = threading.Lock()


def default_registry() -> MetricsRegistry:
    """The process-wide registry (created on first use, with the system
    counter groups pre-adopted)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            from repro.obs import counters as _counters

            _DEFAULT = MetricsRegistry()
            for g in _counters.GROUPS:
                _DEFAULT.counter_group(g)
        return _DEFAULT


class Timer:
    """Tiny context helper: ``with Timer() as t: ... ; t.ms``."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dt = time.perf_counter() - self.t0
        self.ms = self.dt * 1e3
        return False
