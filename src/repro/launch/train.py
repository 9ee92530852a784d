import os
import sys

if __name__ == "__main__" and "--devices" in sys.argv:
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_n}"

"""Training launcher.

Runs the fault-tolerant trainer on any assigned architecture.  On this CPU
container the default is the reduced config on 1 device; ``--devices N``
(must be first jax touch) creates N placeholder devices and shards the step
over a (data × model) debug mesh, exercising the real distribution path.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b \
        --devices 4 --mesh 2x2 --steps 10
"""

import argparse
import dataclasses

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x2")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import ARCHS
    from repro.data.stream import SyntheticStream
    from repro.distributed.sharding import make_shardings, param_pspecs
    from repro.launch.mesh import make_mesh
    from repro.models.factory import reduced_config
    from repro.optim.adamw import AdamW, warmup_cosine
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = ARCHS[args.arch] if args.full else reduced_config(ARCHS[args.arch])
    d_data, d_model = (int(x) for x in args.mesh.split("x"))
    if d_data * d_model > 1:
        mesh = make_mesh((d_data, d_model), ("data", "model"))
        # reduced configs need kv heads divisible by the model axis
        if cfg.num_kv_heads % d_model and cfg.num_kv_heads < d_model:
            cfg = dataclasses.replace(cfg, num_kv_heads=cfg.num_heads)
    else:
        mesh = None

    tcfg = TrainerConfig(
        total_steps=args.steps,
        ckpt_every=max(args.steps // 2, 1),
        ckpt_dir=args.ckpt_dir,
        metric_window=32,
        log_every=max(args.steps // 10, 1),
        compress_grads=args.compress_grads,
    )
    stream = SyntheticStream(cfg, batch=args.batch, seq=args.seq, seed=0)
    opt = AdamW(learning_rate=warmup_cosine(1e-3, 2, args.steps))
    trainer = Trainer(cfg, tcfg, opt, stream)
    state = trainer.resume_or_init(jax.random.key(0))

    if mesh is not None:
        pspec = param_pspecs(cfg, jax.eval_shape(lambda: state.params), tp=d_model)
        sh = make_shardings(mesh, pspec)
        params = jax.tree.map(jax.device_put, state.params, sh)
        state = dataclasses.replace(state, params=params)
        print(f"mesh {args.mesh}: params sharded over {d_model}-way model axis")
        with mesh:
            state = trainer.run(state)
    else:
        state = trainer.run(state)

    print(f"done at step {int(state.step)}")
    for h in trainer.history[-3:]:
        print(f"  step {h['step']:4d} loss={h['loss']:.4f} "
              f"win_mean={h['win/loss_mean']:.4f}")


if __name__ == "__main__":
    main()
