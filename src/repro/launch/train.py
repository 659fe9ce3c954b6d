"""Training launcher CLI.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --seq-len 128 --batch 16 --ckpt-dir /tmp/ckpt

Uses the host's real devices (make_host_mesh); the production-mesh path is
exercised by the dry-run.  Supports restart (just rerun with the same
--ckpt-dir), grad accumulation, 1-bit gradient compression and the smoke
(reduced) configs for CPU-scale runs.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.checkpoint.ckpt import Checkpointer
from repro.configs import base
from repro.data.synthetic import SyntheticStream
from repro.launch import mesh as mesh_lib
from repro.launch.runtime import use_compile_cache
from repro.models.lm import build_model
from repro.optim.adamw import AdamW
from repro.optim.schedules import warmup_cosine
from repro.train import ft
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="smollm-135m",
                   choices=list(base.ARCH_IDS))
    p.add_argument("--smoke", action="store_true", default=True,
                   help="use the reduced same-family config (CPU scale)")
    p.add_argument("--full", dest="smoke", action="store_false")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--compress-grads", action="store_true")
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--model-parallel", type=int, default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    cfg = (base.get_smoke_config(args.arch) if args.smoke
           else base.get_config(args.arch))
    use_compile_cache()
    model = build_model(cfg)
    mesh = mesh_lib.make_host_mesh(model_axis=args.model_parallel)
    opt = AdamW(lr=args.lr, schedule=warmup_cosine(args.steps // 10 + 1,
                                                   args.steps),
                moment_dtype=jnp.dtype(cfg.optim_moment_dtype))
    trainer = Trainer(model, opt, mesh,
                      TrainerConfig(grad_accum=args.grad_accum,
                                    compress_grads=args.compress_grads,
                                    seed=args.seed))
    stream = SyntheticStream(cfg, args.seq_len, args.batch, seed=args.seed)
    ckpt = Checkpointer(args.ckpt_dir)
    print(f"[train] {cfg.name} params="
          f"{sum(x.size for x in jax.tree.leaves(trainer.init_state().params)):,} "
          f"mesh={dict(mesh.shape)} steps={args.steps}")
    ft.run(trainer, stream, ckpt, steps=args.steps,
           ckpt_every=args.ckpt_every)


if __name__ == "__main__":
    main()
