"""Serving launcher CLI: convert-to-deploy + batched generation.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --batch 4 --prompt-len 16 --new-tokens 32 [--full]

``--full`` serves the published widths; the default is the reduced smoke
config (CPU scale).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import base
from repro.launch.runtime import deploy_params, use_compile_cache
from repro.models.lm import build_model
from repro.serve.engine import CacheConfig, ServeConfig, ServeEngine


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="smollm-135m",
                   choices=list(base.ARCH_IDS))
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--new-tokens", type=int, default=32)
    p.add_argument("--max-len", type=int, default=0)
    p.add_argument("--sampler", default="greedy",
                   choices=["greedy", "temperature", "top_k"])
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--full", action="store_true",
                   help="published widths (default: the reduced smoke "
                        "config)")
    args = p.parse_args()

    cfg = (base.get_config(args.arch) if args.full
           else base.get_smoke_config(args.arch))
    if cfg.skip_decode:
        raise SystemExit(f"{args.arch} is encoder-only: no decode face")
    use_compile_cache()
    model = build_model(cfg)
    dparams = deploy_params(model, args.seed)
    max_len = args.max_len or (args.prompt_len + args.new_tokens +
                               cfg.frontend_tokens + 8)
    eng = ServeEngine(model, dparams,
                      ServeConfig(sampler=args.sampler,
                                  temperature=args.temperature,
                                  seed=args.seed,
                                  cache=CacheConfig(max_len=max_len)))
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    kw = {}
    if cfg.frontend_tokens:
        kw["frontend_embeds"] = rng.standard_normal(
            (args.batch, cfg.frontend_tokens, model.frontend_dim),
            dtype=np.float32)
    t0 = time.perf_counter()
    toks, report = eng.generate(prompts, max_new_tokens=args.new_tokens,
                                **kw)
    dt = time.perf_counter() - t0
    tps = args.batch * args.new_tokens / dt
    print(f"[serve] {cfg.name}: generated {toks.shape} in {dt:.2f}s "
          f"({tps:.1f} tok/s)")
    print(f"[serve] binary KV cache: {report['total_bytes']:.0f} B "
          f"({report['compression_vs_bf16']:.1f}x smaller than bf16 KV)")
    print("[serve] sample:", toks[0, :16].tolist())


if __name__ == "__main__":
    main()
