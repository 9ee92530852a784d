"""Keyed window store ≡ the per-key per-element reference, as hypothesis
properties: any key mix, window and chunk split, and chunks that mix none,
a few or only never-seen keys (the admission fast path, small batched
admissions, admission-heavy chunks)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.keyed import KeyedChunkedStream
from repro.core.reference import per_key_reference

from keyed_cases import MONOID_CASES, tree_equal, val_list


# ---------------------------------------------------------------------------
# Hypothesis property sweep
# ---------------------------------------------------------------------------


def test_keyed_stream_property():
    hyp = pytest.importorskip("hypothesis")
    st_mod = pytest.importorskip("hypothesis.strategies")
    given, settings, st = hyp.given, hyp.settings, st_mod

    @given(
        data=st.data(),
        name=st.sampled_from(sorted(MONOID_CASES)),
        window=st.integers(1, 9),
        chunk=st.integers(2, 24),
        universe=st.integers(1, 12),
    )
    @settings(max_examples=30, deadline=None)
    def run(data, name, window, chunk, universe):
        make, gen = MONOID_CASES[name]
        m = make()
        T = data.draw(st.integers(1, 60))
        local = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
        keys = local.integers(0, universe, T).astype(np.int32)
        if name == "affine_i32":
            vals = (
                jnp.asarray(local.integers(-4, 4, T), jnp.int32),
                jnp.asarray(local.integers(-5, 5, T), jnp.int32),
            )
        elif name == "m4":
            vals = jnp.asarray(local.integers(-9, 9, T), jnp.float32)
        else:
            vals = jnp.asarray(local.integers(-9, 9, T), jnp.int32)
        eng = KeyedChunkedStream(m, window, slots=universe + 1, chunk=chunk)
        _, ys = eng.stream(keys, vals)
        ref = per_key_reference(m, keys, val_list(vals), window)
        assert tree_equal(ys, ref)

    run()


def test_keyed_new_key_mix_property():
    """Chunks mixing 0 / few / many genuinely-new keys: the admission fast
    path (no new keys), small batched admissions, and admission-heavy
    chunks all reproduce the per-element reference bit-exactly."""
    hyp = pytest.importorskip("hypothesis")
    st_mod = pytest.importorskip("hypothesis.strategies")
    given, settings, st = hyp.given, hyp.settings, st_mod

    @given(
        data=st.data(),
        name=st.sampled_from(["sum_i32", "affine_i32"]),
        window=st.integers(1, 8),
        chunk=st.integers(4, 16),
    )
    @settings(max_examples=25, deadline=None)
    def run(data, name, window, chunk):
        make, gen = MONOID_CASES[name]
        m = make()
        local = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
        n_chunks = data.draw(st.integers(2, 6))
        # per-chunk count of NEVER-seen keys: 0 → all-hit fast path,
        # small → a one-round batched admission, chunk-many → every row new
        mixes = [
            data.draw(st.sampled_from([0, 1, 2, chunk]))
            for _ in range(n_chunks)
        ]
        next_new = 0
        keys = []
        for n_new in mixes:
            fresh = list(range(next_new, next_new + n_new))
            next_new += n_new
            pool = max(next_new, 1)
            old = local.integers(0, pool, chunk - n_new)
            ck = np.concatenate([np.asarray(fresh, np.int64), old])
            local.shuffle(ck)
            keys.append(ck)
        keys = np.concatenate(keys).astype(np.int32)
        T = len(keys)
        vals = gen(T) if name != "affine_i32" else (
            jnp.asarray(local.integers(-4, 4, T), jnp.int32),
            jnp.asarray(local.integers(-5, 5, T), jnp.int32),
        )
        eng = KeyedChunkedStream(m, window, slots=next_new + chunk + 1,
                                 chunk=chunk)
        _, ys = eng.stream(keys, vals)
        ref = per_key_reference(m, keys, val_list(vals), window)
        assert tree_equal(ys, ref)

    run()
