"""Continuous-batching serving demo on pooled binary KV caches.

Feeds a mixed-length request stream through the slot-pool engine: requests
admit into free cache slots (ragged right-padded prefill), decode in ONE
pooled step per token through the fully binary KV path (K packed along d_h,
V^T packed along the sequence, probs packed in flight), and retire on their
token budget with immediate backfill from the waiting queue.  Reports
tokens/s, slot utilization and the binary-cache memory win.

With ``--paged`` the per-slot rings become a shared page arena + block
tables: slots hold only the pages their tokens occupy, retirement returns
them instantly, and an undersized arena (``--num-pages``) preempts the
lowest-priority slot instead of deadlocking (docs/serving.md walks this
exact run).

Frontend (vlm/audio) archs serve via the static equal-length path.

Run:  PYTHONPATH=src python examples/serve_engine.py \
          [--arch smollm-135m|mixtral-8x22b|hymba-1.5b|xlstm-350m] \
          [--paged [--num-pages N]] [--full]

``--full`` serves the published widths; the default is the reduced smoke
config (CPU scale).
"""
import argparse
import time

import numpy as np

from repro.configs import base
from repro.launch.runtime import deploy_params, use_compile_cache
from repro.models.lm import build_model
from repro.serve.engine import (CacheConfig, PolicyConfig, Request,
                                ServeConfig, ServeEngine, SpecConfig)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="smollm-135m",
                   choices=[a for a in base.ARCH_IDS
                            if not base.get_config(a).skip_decode])
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--min-prompt", type=int, default=4)
    p.add_argument("--max-prompt", type=int, default=16)
    p.add_argument("--new-tokens", type=int, default=16)
    p.add_argument("--paged", action="store_true",
                   help="page-arena KV cache: slots own only the pages "
                        "their tokens occupy; exhaustion preempts instead "
                        "of deadlocking")
    p.add_argument("--num-pages", type=int, default=0,
                   help="arena pages for the full-attention group "
                        "(0 = fully provisioned)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked/streamed prefill width (multiple of 32; "
                        "0 = whole-wave prefill).  Long prompts stream in "
                        "one chunk per engine step, interleaved with "
                        "decode, bounding TTFT for the short requests "
                        "sharing the pool")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decode: draft k tokens per slot per "
                        "step and batch-verify them in one pooled forward "
                        "(0 = off).  Greedy output stays bit-identical; "
                        "the report shows the realized accept rate")
    p.add_argument("--spec-draft-layers", type=int, default=1,
                   help="layer-truncated draft depth (shares the trunk's "
                        "packed weights)")
    p.add_argument("--full", action="store_true",
                   help="published widths (default: the reduced smoke "
                        "config)")
    args = p.parse_args()

    cfg = (base.get_config(args.arch) if args.full
           else base.get_smoke_config(args.arch))
    use_compile_cache()
    model = build_model(cfg)
    dparams = deploy_params(model, 0)
    max_len = args.max_prompt + args.new_tokens + cfg.frontend_tokens + 8
    # frontend archs serve via the static path, which is contiguous-only
    paged = args.paged and not cfg.frontend_tokens
    if args.paged and not paged:
        print(f"[{cfg.name}] frontend arch serves static: --paged ignored")
    eng = ServeEngine(model, dparams, ServeConfig(
        num_slots=args.slots,
        cache=CacheConfig(max_len=max_len, paged=paged,
                          num_pages=args.num_pages or None),
        policy=PolicyConfig(prefill_chunk=args.prefill_chunk or None),
        spec=SpecConfig(k=args.spec_k or None,
                        draft_layers=args.spec_draft_layers)))

    rng = np.random.default_rng(0)
    if cfg.frontend_tokens:
        # frontend archs: static equal-length batch (continuous batching is
        # token-decoder-only)
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.slots, args.max_prompt)).astype(np.int32)
        fe = rng.standard_normal(
            (args.slots, cfg.frontend_tokens, model.frontend_dim),
            dtype=np.float32)
        t0 = time.perf_counter()
        out, report = eng.generate(prompts, max_new_tokens=args.new_tokens,
                                   frontend_embeds=fe)
        total = time.perf_counter() - t0
        n_tok = out.size
        print(f"[{cfg.name}] static batch: {n_tok} tokens in {total:.2f}s "
              f"({n_tok / total:.1f} tok/s)")
    else:
        reqs = [Request(rid=i,
                        tokens=rng.integers(
                            0, cfg.vocab_size,
                            (int(rng.integers(args.min_prompt,
                                              args.max_prompt + 1)),)
                        ).astype(np.int32),
                        max_new_tokens=args.new_tokens)
                for i in range(args.requests)]
        print(f"[{cfg.name}] {len(reqs)} requests, prompt lens "
              f"{[len(r.tokens) for r in reqs]}, {args.slots} slots")
        t0 = time.perf_counter()
        results, report = eng.serve(reqs)
        total = time.perf_counter() - t0
        n_tok = sum(len(v) for v in results.values())
        print(f"  {n_tok} tokens in {total:.2f}s ({n_tok / total:.1f} tok/s)"
              f"; slot utilization "
              f"{report['slot_utilization'] * 100:.0f}% over "
              f"{report['decode_steps']:.0f} pooled decode steps, "
              f"{report['prefill_batches']:.0f} admission waves")
        if "pages_total" in report:
            print(f"  page arena: {report['pages_total']:.0f} pages, peak "
                  f"{report['peak_page_utilization'] * 100:.0f}% used, "
                  f"{report['page_fragmentation'] * 100:.1f}% internal "
                  f"fragmentation, "
                  f"{report['preemptions']:.0f} preemptions")
        if "spec_accept_rate" in report:
            print(f"  speculative: accept rate "
                  f"{report['spec_accept_rate'] * 100:.0f}%, "
                  f"{report['spec_tokens_per_step']:.2f} tokens per "
                  f"verify step")
        for i in range(min(2, len(reqs))):
            print(f"  req {i}: {results[i][:10].tolist()}")
    print(f"binary KV cache: {report['total_bytes']:.0f} B total, "
          f"{report['compression_vs_bf16']:.1f}x smaller than bf16 caches")


if __name__ == "__main__":
    main()
