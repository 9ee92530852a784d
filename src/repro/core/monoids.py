"""Monoid framework for sliding-window aggregation (paper §2.2).

A monoid is ``(S, combine, identity)`` with ``combine`` associative and
``identity`` a two-sided unit.  Following the paper's lift/combine/lower
framework [Tangwongsan et al. 2015], an aggregation is specified by three
functions over three types ``In -> Agg -> Out``:

  * ``lift(e: In) -> Agg``       — applied once on arrival,
  * ``combine(a: Agg, b: Agg)``  — the monoid operator (infix ``⊗``),
  * ``lower(v: Agg) -> Out``     — applied to query results.

``Agg`` elements are arbitrary JAX pytrees with static structure and shapes,
so they can live inside ring buffers, be vmapped, sharded, and carried through
``lax`` control flow.  ``combine`` must NOT assume commutativity: the SWAG
algorithms always pass the *older* operand on the left.

Monoids are plain (static) Python objects, not pytrees — they hold functions.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Monoid:
    """An aggregation monoid with the paper's lift/combine/lower framework.

    Attributes:
      name: identifier used in registries / benchmarks.
      identity: () -> Agg, the unit element ``1``.
      combine: (Agg, Agg) -> Agg, associative; older operand first.
      lift: (In) -> Agg.
      lower: (Agg) -> Out.
      commutative: algebraic property (Table 1 of the paper).
      invertible: True iff ``inverse_front`` is available.
      inverse_front: (Agg, Agg) -> Agg.  ``inverse_front(agg, oldest)``
        removes the *front* element from a window aggregate:
        ``inverse_front(lift(e0) ⊗ r, lift(e0)) == r``.  Only defined for
        invertible monoids (used by the subtract-on-evict baseline).
      lift_batch: optional (values (N,), mask (N,) bool) -> Agg, the fold
        of ``lift`` over the masked values built in one pass; equal to the
        fold up to the monoid's own reassociation error.  Read through
        :func:`repro.core.event_time.fold_values`.
    """

    name: str
    identity: Callable[[], PyTree]
    combine: Callable[[PyTree, PyTree], PyTree]
    lift: Callable[[Any], PyTree]
    lower: Callable[[PyTree], Any]
    commutative: bool = False
    invertible: bool = False
    inverse_front: Optional[Callable[[PyTree, PyTree], PyTree]] = None
    lift_batch: Optional[Callable[[Any, Any], PyTree]] = None

    def __repr__(self) -> str:  # keep pytest parametrize ids short
        return f"Monoid({self.name})"


def counting(monoid: Monoid):
    """Wrap ``monoid`` so every ``combine`` invocation bumps a Python counter.

    Only meaningful in eager (non-traced) execution, where our SWAG
    implementations execute exactly the branch the paper's pseudocode would.
    Returns ``(wrapped_monoid, counter)`` where ``counter`` is a
    :class:`repro.obs.counters.Counter` — ``counter.count`` is the number of
    ⊗-invocations so far and ``counter.reset()`` zeroes it.
    """
    # lazy import: obs.registry imports this module for the KLL sketch, so
    # the reverse edge must not exist at module load
    from repro.obs.counters import Counter

    counter = Counter()

    def combine(a, b):
        counter.inc()
        return monoid.combine(a, b)

    def inverse_front(agg, oldest):
        counter.inc()
        return monoid.inverse_front(agg, oldest)

    wrapped = dataclasses.replace(
        monoid,
        name=monoid.name + "#counted",
        combine=combine,
        inverse_front=inverse_front if monoid.invertible else None,
    )
    return wrapped, counter


# ---------------------------------------------------------------------------
# Sum-like monoids (invertible, commutative — Table 1 row 1)
# ---------------------------------------------------------------------------


def sum_monoid(dtype=jnp.float32) -> Monoid:
    zero = functools.partial(jnp.zeros, (), dtype)
    return Monoid(
        name=f"sum_{jnp.dtype(dtype).name}",
        identity=zero,
        combine=lambda a, b: a + b,
        lift=lambda e: jnp.asarray(e, dtype),
        lower=lambda v: v,
        commutative=True,
        invertible=True,
        inverse_front=lambda agg, oldest: agg - oldest,
    )


def count_monoid(dtype=jnp.int32) -> Monoid:
    return Monoid(
        name="count",
        identity=functools.partial(jnp.zeros, (), dtype),
        combine=lambda a, b: a + b,
        lift=lambda e: jnp.ones((), dtype),
        lower=lambda v: v,
        commutative=True,
        invertible=True,
        inverse_front=lambda agg, oldest: agg - oldest,
    )


def mean_monoid(dtype=jnp.float32) -> Monoid:
    """Arithmetic mean as a (sum, count) pair monoid."""

    def identity():
        return {"s": jnp.zeros((), dtype), "n": jnp.zeros((), jnp.int32)}

    return Monoid(
        name="mean",
        identity=identity,
        combine=lambda a, b: {"s": a["s"] + b["s"], "n": a["n"] + b["n"]},
        lift=lambda e: {"s": jnp.asarray(e, dtype), "n": jnp.ones((), jnp.int32)},
        lower=lambda v: v["s"] / jnp.maximum(v["n"], 1).astype(dtype),
        commutative=True,
        invertible=True,
        inverse_front=lambda agg, old: {"s": agg["s"] - old["s"], "n": agg["n"] - old["n"]},
    )


def geomean_monoid(dtype=jnp.float32) -> Monoid:
    """Geometric mean — the paper's medium-cost operator (§7): log-sum + count."""

    def identity():
        return {"ls": jnp.zeros((), dtype), "n": jnp.zeros((), jnp.int32)}

    return Monoid(
        name="geomean",
        identity=identity,
        combine=lambda a, b: {"ls": a["ls"] + b["ls"], "n": a["n"] + b["n"]},
        lift=lambda e: {"ls": jnp.log(jnp.asarray(e, dtype)), "n": jnp.ones((), jnp.int32)},
        lower=lambda v: jnp.exp(v["ls"] / jnp.maximum(v["n"], 1).astype(dtype)),
        commutative=True,
        invertible=True,
        inverse_front=lambda agg, old: {"ls": agg["ls"] - old["ls"], "n": agg["n"] - old["n"]},
    )


def variance_monoid(dtype=jnp.float32) -> Monoid:
    """Welford/Chan parallel-merge variance: (n, mean, M2) — associative."""

    def identity():
        return {
            "n": jnp.zeros((), dtype),
            "mu": jnp.zeros((), dtype),
            "m2": jnp.zeros((), dtype),
        }

    def combine(a, b):
        n = a["n"] + b["n"]
        safe_n = jnp.maximum(n, 1.0)
        delta = b["mu"] - a["mu"]
        mu = a["mu"] + delta * b["n"] / safe_n
        m2 = a["m2"] + b["m2"] + delta * delta * a["n"] * b["n"] / safe_n
        # Merging with the identity (n == 0) must be exact:
        mu = jnp.where(a["n"] == 0, b["mu"], jnp.where(b["n"] == 0, a["mu"], mu))
        return {"n": n, "mu": mu, "m2": m2}

    return Monoid(
        name="variance",
        identity=identity,
        combine=combine,
        lift=lambda e: {
            "n": jnp.ones((), dtype),
            "mu": jnp.asarray(e, dtype),
            "m2": jnp.zeros((), dtype),
        },
        lower=lambda v: v["m2"] / jnp.maximum(v["n"], 1.0),
        commutative=True,
        invertible=False,
    )


# ---------------------------------------------------------------------------
# Max-like monoids (non-invertible — Table 1 row 2)
# ---------------------------------------------------------------------------


def max_monoid(dtype=jnp.float32) -> Monoid:
    neg_inf = jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo(dtype).min
    return Monoid(
        name=f"max_{jnp.dtype(dtype).name}",
        identity=lambda: jnp.full((), neg_inf, dtype),
        combine=jnp.maximum,
        lift=lambda e: jnp.asarray(e, dtype),
        lower=lambda v: v,
        commutative=True,
        invertible=False,
    )


def min_monoid(dtype=jnp.float32) -> Monoid:
    pos_inf = jnp.finfo(dtype).max if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo(dtype).max
    return Monoid(
        name=f"min_{jnp.dtype(dtype).name}",
        identity=lambda: jnp.full((), pos_inf, dtype),
        combine=jnp.minimum,
        lift=lambda e: jnp.asarray(e, dtype),
        lower=lambda v: v,
        commutative=True,
        invertible=False,
    )


def maxcount_monoid(dtype=jnp.float32) -> Monoid:
    """The paper's running example (§2.2): count of occurrences of the max."""

    def identity():
        neg_inf = jnp.finfo(dtype).min
        return {"m": jnp.full((), neg_inf, dtype), "c": jnp.zeros((), jnp.int32)}

    def combine(a, b):
        gt = a["m"] > b["m"]
        lt = a["m"] < b["m"]
        m = jnp.maximum(a["m"], b["m"])
        c = jnp.where(gt, a["c"], jnp.where(lt, b["c"], a["c"] + b["c"]))
        return {"m": m, "c": c}

    return Monoid(
        name="maxcount",
        identity=identity,
        combine=combine,
        lift=lambda e: {"m": jnp.asarray(e, dtype), "c": jnp.ones((), jnp.int32)},
        lower=lambda v: v["c"],
        commutative=True,
        invertible=False,
    )


def argmax_monoid(dtype=jnp.float32) -> Monoid:
    """argMax with earliest-position tie-break — NON-commutative.

    ``lift`` takes ``(value, position)``.  Ties keep the *left* (older)
    operand, so operand order matters: a genuine non-commutative monoid for
    exercising the SWAG algorithms' ordering discipline.
    """

    def identity():
        neg_inf = jnp.finfo(dtype).min
        return {"m": jnp.full((), neg_inf, dtype), "i": jnp.full((), -1, jnp.int32)}

    def combine(a, b):
        keep_a = a["m"] >= b["m"]  # tie -> older (left) wins
        return {
            "m": jnp.where(keep_a, a["m"], b["m"]),
            "i": jnp.where(keep_a, a["i"], b["i"]),
        }

    def lift(e):
        v, pos = e
        return {"m": jnp.asarray(v, dtype), "i": jnp.asarray(pos, jnp.int32)}

    return Monoid(
        name="argmax",
        identity=identity,
        combine=combine,
        lift=lift,
        lower=lambda v: v["i"],
        commutative=False,
        invertible=False,
    )


def m4_monoid(dtype=jnp.float32) -> Monoid:
    """M4 aggregation [Jugel et al.]: (min, max, first, last) — NON-commutative.

    ``first``/``last`` depend on operand order.  ``n`` tracks emptiness so the
    identity behaves as a true unit.
    """

    def identity():
        return {
            "min": jnp.full((), jnp.finfo(dtype).max, dtype),
            "max": jnp.full((), jnp.finfo(dtype).min, dtype),
            "first": jnp.zeros((), dtype),
            "last": jnp.zeros((), dtype),
            "n": jnp.zeros((), jnp.int32),
        }

    def combine(a, b):
        a_empty = a["n"] == 0
        b_empty = b["n"] == 0
        return {
            "min": jnp.minimum(a["min"], b["min"]),
            "max": jnp.maximum(a["max"], b["max"]),
            "first": jnp.where(a_empty, b["first"], a["first"]),
            "last": jnp.where(b_empty, a["last"], b["last"]),
            "n": a["n"] + b["n"],
        }

    def lift(e):
        v = jnp.asarray(e, dtype)
        return {"min": v, "max": v, "first": v, "last": v, "n": jnp.ones((), jnp.int32)}

    return Monoid(
        name="m4",
        identity=identity,
        combine=combine,
        lift=lift,
        lower=lambda v: jnp.stack([v["min"], v["max"], v["first"], v["last"]]),
        commutative=False,
        invertible=False,
    )


def logsumexp_monoid(dtype=jnp.float32) -> Monoid:
    """Numerically-stable streaming logsumexp (softmax denominators)."""

    neg_inf = jnp.finfo(dtype).min

    def combine(a, b):
        m = jnp.maximum(a, b)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        out = m_safe + jnp.log(
            jnp.exp(a - m_safe) + jnp.exp(b - m_safe)
        )
        return jnp.where(m <= neg_inf / 2, m, out).astype(dtype)

    return Monoid(
        name="logsumexp",
        identity=lambda: jnp.full((), neg_inf, dtype),
        combine=combine,
        lift=lambda e: jnp.asarray(e, dtype),
        lower=lambda v: v,
        commutative=True,
        invertible=False,
    )


# ---------------------------------------------------------------------------
# Mergeable sketches (non-invertible, commutative — Table 1 row 3)
# ---------------------------------------------------------------------------

_HASH_PRIMES = np.array(
    [0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1, 0xFD7046C5],
    dtype=np.uint32,
)


def _hash_u32(x: jax.Array, seed: int) -> jax.Array:
    """Cheap xorshift-multiply hash on uint32 lanes (vectorized)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_HASH_PRIMES[seed % len(_HASH_PRIMES)])
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def bloom_monoid(num_words: int = 64, num_hashes: int = 4) -> Monoid:
    """Bloom filter — the paper's expensive operator (§7).

    Agg = uint32[num_words] bit array; combine = bitwise OR (non-invertible).
    ``num_words * 32`` bits total.  Use :func:`bloom_contains` on a query
    result for membership tests.
    """

    nbits = num_words * 32

    def lift(e):
        e = jnp.asarray(e)
        filt = jnp.zeros((num_words,), jnp.uint32)
        for k in range(num_hashes):
            h = _hash_u32(e, k) % nbits
            word, bit = h // 32, h % 32
            filt = filt.at[word].set(filt[word] | (jnp.uint32(1) << bit))
        return filt

    return Monoid(
        name=f"bloom{nbits}",
        identity=lambda: jnp.zeros((num_words,), jnp.uint32),
        combine=jnp.bitwise_or,
        lift=lift,
        lower=lambda v: v,
        commutative=True,
        invertible=False,
    )


def bloom_contains(filt: jax.Array, e, num_hashes: int = 4) -> jax.Array:
    nbits = filt.shape[-1] * 32
    hit = jnp.array(True)
    for k in range(num_hashes):
        h = _hash_u32(jnp.asarray(e), k) % nbits
        word, bit = h // 32, h % 32
        hit = hit & ((filt[..., word] >> bit) & 1).astype(bool)
    return hit


def countmin_monoid(rows: int = 4, width: int = 64) -> Monoid:
    """Count-min sketch; merge = elementwise add.  Estimate via
    :func:`countmin_estimate`.  (Merge is formally invertible but the
    estimate is not — we expose it as invertible for subtract-on-evict.)"""

    def lift(e):
        e = jnp.asarray(e)
        sk = jnp.zeros((rows, width), jnp.int32)
        for r in range(rows):
            col = _hash_u32(e, r) % width
            sk = sk.at[r, col].add(1)
        return sk

    return Monoid(
        name=f"countmin{rows}x{width}",
        identity=lambda: jnp.zeros((rows, width), jnp.int32),
        combine=lambda a, b: a + b,
        lift=lift,
        lower=lambda v: v,
        commutative=True,
        invertible=True,
        inverse_front=lambda agg, old: agg - old,
    )


def countmin_estimate(sketch: jax.Array, e) -> jax.Array:
    rows, width = sketch.shape[-2:]
    vals = []
    for r in range(rows):
        col = _hash_u32(jnp.asarray(e), r) % width
        vals.append(sketch[..., r, col])
    return jnp.min(jnp.stack(vals, -1), -1)


def kll_monoid(
    k: int = 64,
    levels: int = 8,
    quantiles: tuple = (0.5, 0.95, 0.99),
    dtype=jnp.float32,
) -> Monoid:
    """Mergeable quantile sketch (KLL-style), fixed-size JAX arrays.

    Agg = ``{"items": (levels, k) sorted values (+inf pads), "n": (levels,)
    counts}``; an item at level l carries weight ``2**l``.  ``combine``
    merge-sorts each level and, when a level exceeds k, deterministically
    *compacts*: adjacent sorted pairs collapse to one survivor promoted to
    the next level (the survivor parity alternates with the level count, so
    compaction does not systematically bias a tail).  Everything is
    fixed-shape ``sort``/``where`` — jit/vmap/scan-safe, usable as a
    telemetry product-monoid member.

    Like every sketch, the result is *order-insensitive in distribution but
    not bitwise*: combine is commutative (a sort of the same multiset) and
    associative up to sketch error — rank error ~ O(1/k) of the window
    count, the usual KLL guarantee shape.  Capacity ~ ``k * 2**levels``
    items; beyond that the oldest coarse summaries fall off the top level.

    ``lower`` returns the ``quantiles`` estimates stacked on the last axis
    (leading/batch axes broadcast); :func:`kll_quantiles` evaluates
    arbitrary quantiles on a raw Agg.
    """

    kk = int(k)
    L = int(levels)
    qs = tuple(float(q) for q in quantiles)
    inf = jnp.asarray(jnp.inf, dtype)  # pad sentinel: non-finite by design

    def identity():
        return {
            "items": jnp.full((L, kk), inf, dtype),
            "n": jnp.zeros((L,), jnp.int32),
        }

    def lift(e):
        items = jnp.full((L, kk), inf, dtype).at[0, 0].set(jnp.asarray(e, dtype))
        return {"items": items, "n": jnp.zeros((L,), jnp.int32).at[0].set(1)}

    def compact(merged, n, off):
        """One level's compaction of ``merged`` (ascending, +inf pads last,
        ``n`` real items; even length >= k): slices and selects only.

        Overflow (n > k) compacts the WHOLE level: every sorted adjacent
        pair collapses to one survivor promoted at double weight — the
        ``off`` member of the pair — and an odd item out (the largest)
        stays.  Returns ``(kept (..., k), kept_n, promoted, pairs)``."""
        pairs = jnp.where(n > kk, n // 2, 0)
        pos = jnp.arange(merged.shape[-1])
        idxk = pos[:kk]
        two = merged.reshape(merged.shape[:-1] + (merged.shape[-1] // 2, 2))
        promoted = jnp.where(off[..., None] == 1, two[..., 1], two[..., 0])
        promoted = jnp.where(pos[: two.shape[-2]] < pairs[..., None], promoted, inf)
        kept_n = n - 2 * pairs
        largest = jnp.max(jnp.where(pos < n[..., None], merged, -inf), axis=-1)
        kept = jnp.where(
            (pairs > 0)[..., None],
            jnp.where((idxk == 0) & (kept_n[..., None] > 0), largest[..., None], inf),
            jnp.where(idxk < n[..., None], merged[..., :kk], inf),
        )
        return kept, kept_n, promoted, pairs

    def combine(a, b):
        # survivor parity alternates with the global count (and level), so
        # repeated compactions do not systematically keep the larger (or
        # smaller) of each pair — the classic KLL de-biasing coin, made
        # deterministic
        tot = a["n"].sum(axis=-1) + b["n"].sum(axis=-1)

        def level(carry, x):
            # carry = items promoted from the level below (weight already 2**l)
            promoted, promoted_n = carry
            a_items, b_items, a_n, b_n, l = x
            merged = jnp.sort(
                jnp.concatenate([a_items, b_items, promoted], axis=-1), axis=-1
            )  # (..., 4k) ascending, +inf pads last
            n = a_n + b_n + promoted_n
            kept, kept_n, promoted, promoted_n = compact(merged, n, (tot + l) & 1)
            return (promoted, promoted_n), (kept, kept_n)

        # ONE traced level body scanned over the levels: the program holds
        # one sort and one compaction per combine, not ``levels`` of each
        carry0 = (
            jnp.full(a["items"].shape[:-2] + (2 * kk,), inf, dtype),
            jnp.zeros(a["n"].shape[:-1], jnp.int32),
        )
        xs = (
            jnp.moveaxis(a["items"], -2, 0),
            jnp.moveaxis(b["items"], -2, 0),
            jnp.moveaxis(a["n"], -1, 0),
            jnp.moveaxis(b["n"], -1, 0),
            jnp.arange(L, dtype=jnp.int32),
        )
        _, (items, n) = jax.lax.scan(level, carry0, xs)
        # promotions past the top level fall off (capacity ~ k * 2**levels)
        return {"items": jnp.moveaxis(items, 0, -2), "n": jnp.moveaxis(n, 0, -1)}

    def halve(x, off):
        """The ``off`` member of each adjacent pair of ``x`` (1-D)."""
        two = jnp.pad(x, (0, x.shape[0] % 2), constant_values=inf).reshape(-1, 2)
        return jnp.where(off == 1, two[:, 1], two[:, 0])

    def lift_batch(values, mask):
        # ONE sort of the batch, then up the levels: keep the k largest
        # items (k - 1 when the rest is odd) and compact the rest, on
        # ever-halving slices — O(N) work, no chain of combines, and every
        # level filled, so the capacity is combine's k * (2**levels - 1).
        # The largest stay at the finest levels because the upper tail
        # (p95, p99) is what a latency or value summary is read for.  Up to
        # k items it equals the fold of the lifted values bit-exactly (one
        # sorted level 0).  The parity coin starts from the item count.
        # Work runs on negated values: ascending there is descending here,
        # with the +inf pads still last.
        cur = jnp.sort(jnp.where(mask, -jnp.asarray(values, dtype), inf))
        n = tot = jnp.sum(mask, dtype=jnp.int32)
        idxk = jnp.arange(kk)

        def ascending(neg, kept_n):  # a stored level from negated items
            return jnp.sort(jnp.where(idxk < kept_n, -neg, inf))

        out_items, out_n = [], []
        for l in range(L - 1):
            # width >= k + 1 with an odd tail past k: both pairings below
            # then yield the same number of pairs
            width = max(kk + 1, cur.shape[0])
            width += 1 - (width - kk) % 2
            cur = jnp.pad(cur, (0, width - cur.shape[0]), constant_values=inf)
            kept_n = jnp.where(n > kk, kk - ((n - kk) & 1), n)
            out_items.append(ascending(cur[:kk], kept_n))
            out_n.append(kept_n)
            off = (tot + l) & 1
            promoted = jnp.where(
                kept_n == kk, halve(cur[kk:], off), halve(cur[kk - 1:], off)
            )
            n = (n - kept_n) // 2
            cur = jnp.where(jnp.arange(promoted.shape[0]) < n, promoted, inf)
        # past capacity the top level keeps an even spread of its items
        # (combine drops a compaction's survivors there: mass is lost either
        # way, but no tail is cut off)
        cur = jnp.pad(cur, (0, max(kk - cur.shape[0], 0)), constant_values=inf)
        spread = jnp.where(n > kk, (2 * idxk + 1) * n // (2 * kk), idxk)
        kept_n = jnp.minimum(n, kk)
        out_items.append(ascending(cur[spread], kept_n))
        out_n.append(kept_n)
        return {"items": jnp.stack(out_items), "n": jnp.stack(out_n)}

    def lower(v):
        return kll_quantiles(v, qs)

    return Monoid(
        name=f"kll{kk}x{L}",
        identity=identity,
        # jitted so an eager call reuses one compiled scan per operand shape
        # (an eager lax.scan retraces and recompiles on every call)
        combine=jax.jit(combine),
        lift=lift,
        lower=lower,
        commutative=True,
        invertible=False,
        lift_batch=lift_batch,
    )


def kll_quantiles(agg: PyTree, qs) -> jax.Array:
    """Quantile estimates from a :func:`kll_monoid` Agg (batch axes
    broadcast; returns ``(..., len(qs))``).  Empty sketches yield 0."""
    items = agg["items"]  # (..., L, k)
    L, k = items.shape[-2:]
    flat = items.reshape(items.shape[:-2] + (L * k,))
    level_w = jnp.broadcast_to(
        jnp.repeat(2 ** jnp.arange(L, dtype=jnp.float32), k), flat.shape
    )
    weights = jnp.where(jnp.isfinite(flat), level_w, 0.0)
    order = jnp.argsort(flat, axis=-1)
    svals = jnp.take_along_axis(flat, order, axis=-1)
    swts = jnp.take_along_axis(weights, order, axis=-1)
    cum = jnp.cumsum(swts, axis=-1)
    total = cum[..., -1:]
    outs = []
    for q in qs:
        target = q * total
        idx = jnp.argmax(cum >= target, axis=-1)[..., None]
        val = jnp.take_along_axis(svals, idx, axis=-1)
        outs.append(jnp.where(total > 0, val, 0.0)[..., 0])
    return jnp.stack(outs, axis=-1)


def topk_monoid(k: int = 8, count_dtype=jnp.int32) -> Monoid:
    """SpaceSaving-style fixed-shape heavy hitters over int32 keys.

    Agg = ``{"keys": (k,) int32 (-1 = empty), "counts": (k,)}`` held in
    canonical order (count descending, key ascending on ties — every
    ``combine`` re-canonicalizes, so equal multisets have equal
    representations).  ``combine`` merges the two summaries exactly —
    matching keys sum their counts (a fixed-shape k×k equality match, k is
    small) — then keeps the ``k`` heaviest survivors; the tail truncation
    is the SpaceSaving-style approximation.  Like :func:`kll_monoid`,
    everything is fixed-shape ``where``/``sort`` — jit/vmap/scan-safe, a
    valid telemetry product-monoid member, and usable as a per-key window
    lane in the keyed store.

    Guarantees: exact (and bit-exactly associative/commutative) while the
    union holds ≤ k distinct keys; beyond that, kept counts are lower
    bounds and a key with true frequency above the dropped tail's max
    stays resident — the usual heavy-hitter contract.  ``lift`` takes a
    non-negative int32 key; use :func:`topk_items` to read an Agg.
    """

    kk = int(k)

    def identity():
        return {
            "keys": jnp.full((kk,), -1, jnp.int32),
            "counts": jnp.zeros((kk,), count_dtype),
        }

    def lift(e):
        return {
            "keys": jnp.full((kk,), -1, jnp.int32).at[0].set(
                jnp.asarray(e, jnp.int32)
            ),
            "counts": jnp.zeros((kk,), count_dtype).at[0].set(1),
        }

    def combine(a, b):
        ak, bk = a["keys"], b["keys"]
        # k×k key match: b's count folds into a's matching entry, matched
        # b entries are zeroed (canonical inputs hold each key at most once)
        eq = (ak[..., :, None] == bk[..., None, :]) & (ak[..., :, None] >= 0)
        a_cnt = a["counts"] + jnp.sum(
            jnp.where(eq, b["counts"][..., None, :], 0), axis=-1
        )
        b_cnt = jnp.where(jnp.any(eq, axis=-2), 0, b["counts"])
        keys = jnp.concatenate([ak, bk], axis=-1)
        cnts = jnp.concatenate([a_cnt, b_cnt], axis=-1)
        keys = jnp.where(cnts > 0, keys, -1)
        cnts = jnp.where(keys >= 0, cnts, 0)
        # canonical order: count desc, key asc on ties (empties sort last);
        # keep the k heaviest
        order = jnp.lexsort((keys, -cnts), axis=-1)
        keys = jnp.take_along_axis(keys, order, axis=-1)[..., :kk]
        cnts = jnp.take_along_axis(cnts, order, axis=-1)[..., :kk]
        return {"keys": keys, "counts": cnts}

    return Monoid(
        name=f"topk{kk}",
        identity=identity,
        combine=combine,
        lift=lift,
        lower=lambda v: v,
        commutative=True,
        invertible=False,
    )


def topk_items(agg: PyTree) -> list:
    """``[(key, count), ...]`` of a :func:`topk_monoid` Agg, heaviest first
    (host-side; empty slots elided)."""
    keys = np.asarray(agg["keys"]).ravel()
    counts = np.asarray(agg["counts"]).ravel()
    live = keys >= 0
    return list(zip(keys[live].tolist(), counts[live].tolist()))


def hll_monoid(num_registers: int = 64) -> Monoid:
    """HyperLogLog-style register-max sketch; combine = elementwise max."""

    def lift(e):
        h = _hash_u32(jnp.asarray(e), 0)
        reg = (h % num_registers).astype(jnp.int32)
        # rank = leading-zero count of the remaining bits, +1: rank r with
        # probability 2^-r, the distribution hll_estimate's harmonic-mean
        # estimator assumes (the old +2 shift biased estimates ~2x high)
        rest = _hash_u32(jnp.asarray(e), 1)
        rank = 32 - jnp.floor(jnp.log2(rest.astype(jnp.float32) + 1.0)).astype(jnp.int32)
        regs = jnp.zeros((num_registers,), jnp.int32)
        return regs.at[reg].set(rank)

    return Monoid(
        name=f"hll{num_registers}",
        identity=lambda: jnp.zeros((num_registers,), jnp.int32),
        combine=jnp.maximum,
        lift=lift,
        lower=lambda v: v,
        commutative=True,
        invertible=False,
    )


def hll_estimate(regs) -> jax.Array:
    """Distinct-count estimate from a :func:`hll_monoid` Agg (register
    array, batch axes broadcast) — the standard harmonic-mean estimator
    with the small-range linear-counting correction."""
    regs = jnp.asarray(regs)
    m = regs.shape[-1]
    alpha = 0.7213 / (1.0 + 1.079 / m)
    raw = alpha * m * m / jnp.sum(2.0 ** (-regs.astype(jnp.float32)), axis=-1)
    zeros = jnp.sum(regs == 0, axis=-1)
    linear = m * jnp.log(m / jnp.maximum(zeros, 1).astype(jnp.float32))
    return jnp.where((raw <= 2.5 * m) & (zeros > 0), linear, raw)


# ---------------------------------------------------------------------------
# Non-commutative, non-invertible monoids for systems integration & testing
# ---------------------------------------------------------------------------


def affine_monoid(state_shape: tuple, dtype=jnp.float32) -> Monoid:
    """Composition of affine state maps ``s ↦ d ⊙ s + u`` (SSM/RWKV windows).

    An element represents the map ``s ↦ d*s + u`` with per-channel decay ``d``
    (shape ``state_shape``) and update ``u`` (same shape).  Composition with
    the OLDER map applied first:

        (d_a, u_a) ⊗ (d_b, u_b)  =  (d_a*d_b, d_b*u_a + u_b)

    Associative ✓, non-commutative ✓, non-invertible when any decay is 0 —
    exactly the monoid class DABA exists for.  ``query`` of a window of maps
    applied to a zero initial state yields the *windowed* SSM state: an
    evicting, exact sliding-window recurrence in O(1) worst-case combines per
    token (see core/windowed_state.py).
    """

    def identity():
        return {"d": jnp.ones(state_shape, dtype), "u": jnp.zeros(state_shape, dtype)}

    def combine(a, b):
        return {"d": a["d"] * b["d"], "u": b["d"] * a["u"] + b["u"]}

    def lift(e):
        return {"d": jnp.asarray(e["d"], dtype), "u": jnp.asarray(e["u"], dtype)}

    return Monoid(
        name=f"affine{state_shape}",
        identity=identity,
        combine=combine,
        lift=lift,
        lower=lambda v: v["u"],  # map applied to s0 = 0
        commutative=False,
        invertible=False,
    )


def affine_int_monoid() -> Monoid:
    """Exact-arithmetic affine monoid over Z/2^32 (wraparound int32).

    Exactly associative (no floating-point error), non-commutative and
    non-invertible (a = 0 kills information) — the reference monoid for
    hypothesis property tests where bit-exact oracle equality is asserted.
    lift takes a pair ``(a, b)`` of ints.
    """

    def identity():
        return {"a": jnp.ones((), jnp.int32), "b": jnp.zeros((), jnp.int32)}

    def combine(x, y):
        return {"a": x["a"] * y["a"], "b": y["a"] * x["b"] + y["b"]}

    def lift(e):
        a, b = e
        return {"a": jnp.asarray(a, jnp.int32), "b": jnp.asarray(b, jnp.int32)}

    return Monoid(
        name="affine_i32",
        identity=identity,
        combine=combine,
        lift=lift,
        lower=lambda v: v["b"],
        commutative=False,
        invertible=False,
    )


def matrix_monoid(k: int = 2, dtype=jnp.float32) -> Monoid:
    """k×k matrix product monoid — non-commutative, generally non-invertible."""

    return Monoid(
        name=f"mat{k}x{k}",
        identity=lambda: jnp.eye(k, dtype=dtype),
        combine=lambda a, b: a @ b,
        lift=lambda e: jnp.asarray(e, dtype).reshape(k, k),
        lower=lambda v: v,
        commutative=False,
        invertible=False,
    )


# ---------------------------------------------------------------------------
# Combinators
# ---------------------------------------------------------------------------


def product_monoid(members: dict[str, Monoid]) -> Monoid:
    """Pointwise product of named monoids: Agg = {name: member Agg}.

    One combined element carries N metrics, so a windowed-telemetry update is
    a single monoid operation on a single state (one jitted dispatch) instead
    of N separate windows.  ``lift``/``lower`` map dicts keyed like
    ``members``; algebraic properties are the conjunction of the members'
    (``inverse_front`` exists iff every member is invertible).
    """
    members = dict(members)

    def identity():
        return {k: m.identity() for k, m in members.items()}

    def combine(a, b):
        return {k: m.combine(a[k], b[k]) for k, m in members.items()}

    def lift(e):
        return {k: m.lift(e[k]) for k, m in members.items()}

    def lower(v):
        return {k: m.lower(v[k]) for k, m in members.items()}

    invertible = all(m.invertible for m in members.values())

    def inverse_front(agg, old):
        return {k: m.inverse_front(agg[k], old[k]) for k, m in members.items()}

    return Monoid(
        name="prod[" + ",".join(f"{k}={m.name}" for k, m in members.items()) + "]",
        identity=identity,
        combine=combine,
        lift=lift,
        lower=lower,
        commutative=all(m.commutative for m in members.values()),
        invertible=invertible,
        inverse_front=inverse_front if invertible else None,
    )


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[[], Monoid]] = {
    "sum": sum_monoid,
    "sum_i32": functools.partial(sum_monoid, jnp.int32),
    "sum_i64": functools.partial(sum_monoid, jnp.int64),
    "count": count_monoid,
    "mean": mean_monoid,
    "geomean": geomean_monoid,
    "variance": variance_monoid,
    "max": max_monoid,
    "max_i32": functools.partial(max_monoid, jnp.int32),
    "min": min_monoid,
    "maxcount": maxcount_monoid,
    "argmax": argmax_monoid,
    "m4": m4_monoid,
    "logsumexp": logsumexp_monoid,
    "bloom": bloom_monoid,
    "countmin": countmin_monoid,
    "hll": hll_monoid,
    "kll": kll_monoid,
    "topk": topk_monoid,
    "affine_i32": affine_int_monoid,
    "mat2x2": matrix_monoid,
}


def get_monoid(name: str, **kwargs) -> Monoid:
    if name not in _REGISTRY:
        raise KeyError(f"unknown monoid {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def available_monoids() -> list[str]:
    return sorted(_REGISTRY)
