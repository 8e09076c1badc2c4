"""Training launcher.

Small-scale (CPU, default): --arch coic-paper --steps 50
Production mesh dry config:  --mesh 16x16 (requires that many devices).

Assembles mesh -> sharded train state -> data pipeline -> Trainer with
checkpointing and straggler watch.
"""
from __future__ import annotations

import argparse

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced_config
from repro.data.pipeline import SyntheticLMData, shard_batch
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim.adamw import OptState
from repro.parallel.sharding import RULES_TRAIN, set_activation_sharder
from repro.checkpoint.checkpointer import Checkpointer
from repro.train.trainer import (TrainState, TrainerConfig, init_train_state,
                                 make_train_step)
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="coic-paper")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config of the arch family")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    model = build_model(cfg)

    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    tcfg = TrainerConfig(peak_lr=args.lr, warmup_steps=max(10, args.steps // 10),
                         total_steps=args.steps, microbatches=args.microbatches)

    axes = model.logical_axes()
    shapes = model.init_shapes()
    p_sh = {k: RULES_TRAIN.sharding_for(axes[k], shapes[k].shape, mesh)
            for k in shapes}
    state_sh = TrainState(
        params=p_sh,
        opt=OptState(mu=dict(p_sh), nu=dict(p_sh),
                     count=NamedSharding(mesh, P())),
        step=NamedSharding(mesh, P()))

    state = jax.device_put(init_train_state(model, jax.random.PRNGKey(0), tcfg),
                           state_sh)
    step_fn = jax.jit(make_train_step(model, tcfg),
                      in_shardings=(state_sh, None), out_shardings=(state_sh, None),
                      donate_argnums=(0,))

    data = SyntheticLMData(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        image_patches=cfg.num_image_patches, d_model=cfg.d_model,
        encdec=cfg.family == "encdec", dec_len=max(8, args.seq // 4))
    ckpt = Checkpointer(args.ckpt_dir, keep=3) if args.ckpt_dir else None

    import time
    for step in range(args.steps):
        batch = data.batch_at(step)
        with set_activation_sharder(mesh, RULES_TRAIN), mesh:
            dbatch = shard_batch(batch, mesh, RULES_TRAIN)
            t0 = time.perf_counter()
            state, metrics = step_fn(state, dbatch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {loss:.4f} ({dt*1e3:.1f} ms)", flush=True)
        if ckpt and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, state)
    if ckpt:
        ckpt.wait()
    print(f"final loss {loss:.4f}")


if __name__ == "__main__":
    main()
