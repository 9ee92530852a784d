"""Batched SWAG — partition parallelism (paper §8.2) on SIMD/SPMD hardware.

Maintains B independent sliding windows (one per key/stream partition) as a
single vmapped state, shardable over any mesh axes with **zero cross-window
collectives**.  This is where DABA's worst-case O(1) bound becomes a
throughput property rather than just a latency property (DESIGN.md §2.1):

  * DABA/DABA Lite: ``lax.cond`` → ``select`` under vmap — every lane does
    identical constant work; per-step cost is uniform and independent of the
    per-lane flip phase.
  * Two-Stacks: the flip's data-dependent loop becomes a ``while_loop`` whose
    trip count is the max over all lanes — one lane's O(n) flip stalls the
    whole batch, so batched amortized-O(1) degrades toward O(n / gcd of
    phases).  Measured in benchmarks/bench_batched.py.

Per-lane ``insert``/``evict`` masking supports ragged streams: each step takes
(values, do_insert, do_evict) so different lanes may be at different phases
of fill/slide (dynamic windows per lane).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import swag_base
from repro.core.monoids import Monoid

PyTree = Any

# stream() auto-routes through the chunked bulk engine at or above this many
# steps (for any concrete — cold or warm — initial state); below it the
# per-element scan's lower constant cost wins.
CHUNKED_AUTO_MIN_T = 2048


class BatchedSWAG:
    """Vmapped multi-window SWAG bound to (algo, monoid, capacity).

    All methods are functional: they take and return the batched state.
    ``init(batch)`` allocates ``batch`` lanes.  States are ordinary pytrees —
    shard them with ``jax.device_put(state, NamedSharding(mesh, spec))`` and
    every op stays collective-free.
    """

    def __init__(self, algo, monoid: Monoid, capacity: int):
        self.algo = algo
        self.monoid = monoid
        self.capacity = capacity
        self._chunked_engines = {}  # (window, chunk) -> ChunkedStream
        # jitted final-state rebuild for the chunked path (jit caches per
        # input shape; values have (k, batch) leading -> vmap over axis 1)
        self._bulk_insert = jax.jit(
            jax.vmap(
                functools.partial(swag_base.insert_bulk, algo, monoid),
                in_axes=(0, 1),
            )
        )
        # per-lane bulk evict (k is a (batch,) array: warm lanes may be ragged)
        self._bulk_evict = jax.jit(
            jax.vmap(functools.partial(swag_base.evict_bulk, algo, monoid))
        )
        # fully-vectorized fresh-state rebuild from the last `window` inputs
        # (one log-depth suffix scan, no sequential fixups) — used whenever
        # the stream is long enough to replace the whole window
        self._state_from_chunk = jax.jit(
            jax.vmap(
                lambda vs: swag_base.state_from_chunk(
                    algo, monoid, vs, capacity
                ),
                in_axes=1,
            )
        )

        def _step(state, value, do_insert, do_evict):
            """Masked per-lane step: optionally insert, then optionally evict."""
            state = jax.lax.cond(
                do_insert,
                lambda s: algo.insert(monoid, s, value),
                lambda s: s,
                state,
            )
            state = jax.lax.cond(
                do_evict,
                lambda s: algo.evict(monoid, s),
                lambda s: s,
                state,
            )
            return state

        # jitted: an eager call of a bare vmap (or lax.cond / lax.scan over
        # fresh closures) retraces, and the lax ones recompile, every time
        self._insert = jax.jit(jax.vmap(lambda s, v: algo.insert(monoid, s, v)))
        self._evict = jax.jit(jax.vmap(lambda s: algo.evict(monoid, s)))
        self._query = jax.jit(jax.vmap(lambda s: algo.query(monoid, s)))
        self._step = jax.jit(jax.vmap(_step))
        self._size = jax.jit(jax.vmap(algo.size))
        self._scans = {}  # window -> jitted per-element stream

    def init(self, batch: int) -> PyTree:
        return jax.vmap(lambda _: self.algo.init(self.monoid, self.capacity))(
            jnp.arange(batch)
        )

    def insert(self, state: PyTree, values: PyTree) -> PyTree:
        """Insert one value into every lane (values has leading batch dim)."""
        return self._insert(state, values)

    def evict(self, state: PyTree) -> PyTree:
        return self._evict(state)

    def query(self, state: PyTree) -> PyTree:
        return self._query(state)

    def step(self, state: PyTree, values: PyTree, do_insert, do_evict) -> PyTree:
        """Masked step for ragged / dynamically-sized per-lane windows."""
        return self._step(state, values, do_insert, do_evict)

    def size(self, state: PyTree) -> jax.Array:
        return self._size(state)

    def stream(
        self,
        state: PyTree,
        xs: PyTree,
        window: int,
        *,
        chunked: Optional[bool] = None,
        chunk: Optional[int] = None,
    ):
        """Scan a (T, batch, …) stream through fixed-size-``window`` sliding
        aggregation; returns (final_state, (T, batch) queries).  The standard
        count-based window: insert, evict once size exceeds ``window``.

        Routing: by default (``chunked=None``) streams with T ≥
        ``CHUNKED_AUTO_MIN_T`` whose state is concrete (not traced) with
        every lane size ≤ ``window`` go through the
        :class:`~repro.core.chunked.ChunkedStream` bulk engine (Pallas
        kernels / associative scans, ~3 combines per element).  Warm
        (non-empty) states are included: the engine's carry is initialized
        from the live window via the warm-carry protocol
        (``swag_base.state_to_carry``).  Everything else — small T, traced
        state under jit, overfull lanes — takes the per-element ``lax.scan``.
        ``chunked=True`` forces the bulk path (the caller asserts every lane
        holds ≤ ``window`` elements); ``chunked=False`` forces per-element.
        Outputs agree exactly for integer monoids and up to combine
        reassociation for floats; the bulk path's final state is rebuilt by
        bulk-evicting what would overflow and bulk-inserting the last
        min(T, window) inputs — a valid state with identical window contents
        (and therefore identical query results and future behaviour), not a
        bit-identical internal layout.
        """
        T = jax.tree.leaves(xs)[0].shape[0]
        if chunked is None:
            chunked = False
            if T >= CHUNKED_AUTO_MIN_T:
                sizes = self._concrete_sizes(state)
                chunked = sizes is not None and bool((sizes <= window).all())
        if chunked:
            return self._stream_chunked(state, xs, window, chunk)

        scan = self._scans.get(window)
        if scan is None:

            def scan_step(st, x):
                st = self._insert(st, x)
                st = self._step(
                    st,
                    x,
                    jnp.zeros(self._size(st).shape, bool),
                    self._size(st) > window,
                )
                return st, self._query(st)

            scan = self._scans[window] = jax.jit(
                lambda st, xs: jax.lax.scan(scan_step, st, xs)
            )
        return scan(state, xs)

    def _concrete_sizes(self, state: PyTree):
        try:
            return np.asarray(self.size(state))
        except (jax.errors.TracerArrayConversionError, jax.errors.ConcretizationTypeError):
            return None  # traced under jit: stay on the per-element path

    def _stream_chunked(self, state: PyTree, xs: PyTree, window: int, chunk):
        from repro.core.chunked import ChunkedStream  # local: avoid cycle

        key = (window, chunk)
        cached = self._chunked_engines.get(key)
        if cached is None:  # cache: the engine + jitted carry extraction
            engine = ChunkedStream(self.monoid, window, chunk)
            carry_fn = jax.jit(
                lambda st: engine.init_carry(from_state=st, algo=self.algo)
            )
            cached = self._chunked_engines[key] = (engine, carry_fn)
        engine, carry_fn = cached
        ys = engine.stream(xs, carry=carry_fn(state))
        # Final state: same window contents as the per-element scan.
        T = jax.tree.leaves(xs)[0].shape[0]
        if T >= window:
            # the stream replaces the whole window — build a fresh state from
            # the last `window` inputs, fully vectorized (no sequential loop)
            last = jax.tree.map(lambda a: a[T - window:], xs)
            state = self._state_from_chunk(last)
        else:
            # partial refresh (window > T ≥ CHUNKED_AUTO_MIN_T): evict
            # per-lane what the inserts would overflow, then bulk-insert —
            # evict-first also keeps every lane within the ring capacity
            k = jnp.maximum(self.size(state) + T - window, 0)
            state = self._bulk_evict(state, k)
            state = self._bulk_insert(state, xs)
        return state, ys
