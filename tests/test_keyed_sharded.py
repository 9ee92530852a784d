"""``ShardedKeyedStore``: a hash-sharded key space over a 4-device mesh
reproduces the single-store outputs with zero steady-state collectives
(a subprocess with a host platform device count), and default-ts updates
keep per-shard recency."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.core import monoids

# a hung child fails its test here, inside the per-test limit
# (conftest.TEST_LIMIT_S); the child takes ~15 s on an idle CPU host
SUBPROCESS_S = 180


# ---------------------------------------------------------------------------
# Sharded store (multi-device subprocess)
# ---------------------------------------------------------------------------


_SUBPROCESS_SHARDED = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import monoids
    from repro.core.keyed import KeyedChunkedStream, ShardedKeyedStore

    rng = np.random.default_rng(1)
    T, U, W = 256, 40, 8
    keys = rng.integers(0, U, T).astype(np.int32)
    xs = rng.integers(-9, 9, T).astype(np.int32)
    m = monoids.sum_monoid(jnp.int32)

    mesh = jax.make_mesh((4,), ("data",))
    sh = ShardedKeyedStore(m, W, slots_per_shard=32, mesh=mesh, axis="data")
    state = sh.init_state()
    state, ys, owner = sh.update_chunk(state, jnp.asarray(keys), jnp.asarray(xs))
    y = ShardedKeyedStore.collect(ys, owner)

    eng = KeyedChunkedStream(m, window=W, slots=128, chunk=T)
    _, ref = eng.stream(keys, jnp.asarray(xs))
    assert jnp.array_equal(y, ref)
    # per-shard states are genuinely sharded on the leading axis
    assert state["carry"].sharding.spec[0] == "data"
    # init_state builds each shard on its own device (no stacked copy)
    shards = state["carry"].addressable_shards
    assert sorted(s.device.id for s in shards) == [0, 1, 2, 3]
    assert all(s.data.shape[0] == 1 for s in shards)
    print("OK")
    """
)


def test_sharded_default_ts_keeps_recency():
    """Default (no ts) sharded updates must advance last_used via the
    per-shard tick: a hot key observed every chunk is never TTL-expired,
    and the untouched key (not a hot one) is the LRU/TTL victim."""
    from repro.core.keyed import ShardedKeyedStore

    m = monoids.sum_monoid(jnp.int32)
    mesh = jax.make_mesh((1,), ("data",))
    sh = ShardedKeyedStore(m, 4, slots_per_shard=8, mesh=mesh, axis="data",
                           ttl=5.0)
    st = sh.init_state()
    st, _, _ = sh.update_chunk(st, jnp.asarray([1, 2], jnp.int32),
                               jnp.ones(2, jnp.int32))
    for _ in range(8):  # key 1 stays hot; key 2 goes idle past the ttl
        st, _, _ = sh.update_chunk(st, jnp.asarray([1], jnp.int32),
                                   jnp.ones(1, jnp.int32))
    st1 = jax.tree.map(lambda a: a[0], st)
    agg, found = sh.store.query(st1, jnp.asarray([1, 2], jnp.int32))
    assert bool(found[0]), "hot key must survive TTL sweeps"
    assert not bool(found[1]), "idle key should expire"
    assert int(agg[0]) == 4  # window of the hot key's last 4 ones


def test_sharded_keyed_store_4dev():
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SHARDED],
        capture_output=True, text=True, timeout=SUBPROCESS_S,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
