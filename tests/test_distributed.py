"""Distributed pieces testable in-process: sharding rule validity for every
arch, compression math, and multi-device collectives via a subprocess (the
main process must keep the default 1-device CPU platform)."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES
from repro.distributed.compression import (
    dequantize,
    ef_compress_tree,
    init_error_state,
    quantize,
)
from repro.distributed.sharding import param_pspecs
from repro.models import factory


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_pspecs_divisible(arch):
    """Every sharded dim must divide the 16-way model axis — for all archs,
    including the awkward ones (arctic H=56, qwen2-vl H=12, whisper V=51866,
    grok E=8)."""
    cfg = ARCHS[arch]
    shapes = factory.param_specs(cfg)
    specs = param_pspecs(cfg, shapes, tp=16)

    def check(path, leaf, spec):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax == "model":
                assert dim % 16 == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), shapes, specs,
        is_leaf=lambda x: hasattr(x, "shape"),
    )


@pytest.mark.parametrize("arch", ["arctic-480b", "grok-1-314b"])
def test_fsdp_pspecs_shard_big_leaves(arch):
    """With an fsdp mesh, every multi-MB leaf gains a data axis."""
    cfg = ARCHS[arch]
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    shapes = factory.param_specs(cfg)
    specs = param_pspecs(cfg, shapes, tp=16, fsdp_mesh=FakeMesh())

    bad = []

    def check(path, leaf, spec):
        if leaf.size >= (1 << 22):
            axes = set()
            for ax in tuple(spec):
                if isinstance(ax, tuple):
                    axes.update(ax)
                elif ax:
                    axes.add(ax)
            if "data" not in axes and "pod" not in axes:
                bad.append((path, leaf.shape, spec))

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), shapes, specs,
        is_leaf=lambda x: hasattr(x, "shape"),
    )
    assert not bad, bad


def test_quantize_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(4096), jnp.float32)
    q, s = quantize(x)
    err = float(jnp.abs(dequantize(q, s) - x).max())
    assert err <= float(s) * 0.51  # half-ulp of the int8 grid


def test_error_feedback_unbiased_over_time():
    """Accumulated EF output converges to the accumulated true gradient."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.standard_normal(512) * 1e-3, jnp.float32)
    errs = init_error_state({"g": g_true})
    total = jnp.zeros(512)
    for _ in range(64):
        out, errs = ef_compress_tree({"g": g_true}, errs)
        total = total + out["g"]
    rel = float(jnp.linalg.norm(total - 64 * g_true) / jnp.linalg.norm(64 * g_true))
    assert rel < 0.02, rel


_SUBPROCESS_COLLECTIVE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import compressed_psum_mean
    mesh = jax.make_mesh((8,), ("dp",))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 4096)), jnp.float32)
    f = jax.shard_map(lambda x: compressed_psum_mean(x, "dp"),
                      mesh=mesh, in_specs=P("dp", None),
                      out_specs=P("dp", None), check_vma=False)
    y = f(x)
    ref = jnp.broadcast_to(x.mean(0, keepdims=True), x.shape)
    rel = float(jnp.abs(y - ref).max() / jnp.abs(ref).max())
    assert rel < 0.05, rel
    print("OK", rel)
    """
)


def test_compressed_ring_allreduce_8dev():
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_COLLECTIVE],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(__file__)),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


_SUBPROCESS_SHARDED_TRAIN = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ARCHS
    from repro.models.factory import reduced_config, make_smoke_batch
    from repro.models.transformer import init_params
    from repro.optim.adamw import AdamW
    from repro.train.train_step import init_train_state, make_train_step
    from repro.distributed.sharding import param_pspecs, make_shardings
    import dataclasses

    cfg = dataclasses.replace(reduced_config(ARCHS["llama3.2-1b"]), num_kv_heads=4)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    opt = AdamW(learning_rate=1e-3)
    params = init_params(cfg, jax.random.key(0))
    state = init_train_state(cfg, params, opt, metric_window=8)
    batch = make_smoke_batch(cfg, jax.random.key(1), B=4, S=16)

    # sharded run on the 2x2 mesh
    with mesh:
        pspec = param_pspecs(cfg, jax.eval_shape(lambda: params), tp=2)
        sh_params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspec)
        sh_state = dataclasses.replace(state, params=sh_params)
        step = jax.jit(make_train_step(cfg, opt))
        sh_state2, m_sharded = step(sh_state, batch)

    # single-device reference
    step1 = jax.jit(make_train_step(cfg, opt))
    state2, m_single = step1(state, batch)
    dl = abs(float(m_sharded["loss"]) - float(m_single["loss"]))
    dg = abs(float(m_sharded["grad_norm"]) - float(m_single["grad_norm"]))
    assert dl < 1e-3 and dg < 5e-2, (dl, dg)
    import numpy as np
    pa = jax.tree.leaves(sh_state2.params); pb = jax.tree.leaves(state2.params)
    err = max(float(jnp.abs(a - b).max()) for a, b in zip(pa, pb))
    assert err < 5e-2, err
    print("OK", dl, err)
    """
)


def test_sharded_train_step_matches_single_device():
    """DP×TP=2×2 sharded train step ≡ single-device step (loss/params)."""
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SHARDED_TRAIN],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(__file__)),
    )
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-2000:])
    assert "OK" in r.stdout


_SUBPROCESS_SHARDED_COUNTERS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import monoids
    from repro.core.keyed import ShardedKeyedStore, shard_of_key
    from repro.obs.registry import MetricsRegistry

    mesh = jax.make_mesh((4,), ("data",))
    # slots_per_shard=4 with a 256-key universe: every shard is saturated,
    # evicting constantly and dropping rows whose chunk-local distinct-key
    # count overflows the tiny directory
    sh = ShardedKeyedStore(monoids.sum_monoid(jnp.int32), window=4,
                           slots_per_shard=4, mesh=mesh)
    state = sh.init_state()
    rng = np.random.default_rng(0)
    for _ in range(8):
        keys = jnp.asarray(rng.integers(0, 256, 64), jnp.int32)
        xs = jnp.ones(64, jnp.int32)
        state, ys, owner = sh.update_chunk(state, keys, xs)

    c = jax.device_get(sh.counters(state, per_shard=True))
    # the mesh-wide rollup must equal the per-shard sums, per counter
    for k in ("n_live", "n_evicted", "n_failed", "n_dropped"):
        assert int(c[k]) == int(np.sum(c["per_shard"][k])), (k, c)
    assert c["per_shard"]["n_live"].shape == (4,)
    assert int(c["n_live"]) == 16, c            # all 4x4 slots saturated
    assert int(c["n_evicted"]) > 0, c           # universe >> slots
    assert all(int(v) > 0 for v in c["per_shard"]["n_evicted"]), c

    # attach_obs: one scrape serves the rollup AND {shard="i"} series
    reg = MetricsRegistry()
    sh.attach_obs(reg, lambda: state)
    snap = reg.scrape()
    assert snap["repro_sharded_live_keys"] == 16, snap
    per = [snap['repro_sharded_evictions_total{shard="%d"}' % i]
           for i in range(4)]
    assert sum(per) == snap["repro_sharded_evictions_total"], (per, snap)
    print("OK", int(c["n_evicted"]), int(c["n_dropped"]))
    """
)


def test_sharded_keyed_counters_rollup_4dev():
    """Mesh-wide counter rollup over a 4-shard keyed store: the summed
    totals equal the per-shard values, and the obs collector exposes both
    (the pre-PR-8 blind spot: only shard-local scalars existed)."""
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SHARDED_COUNTERS],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(__file__)),
    )
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-2000:])
    assert "OK" in r.stdout
