"""Static vs continuous vs chunked vs paged scheduling on the binary cache.

Replays the same mixed short/long request trace through the schedulers:

  static      requests grouped into pool-sized waves; every wave pads to
              its longest prompt and decodes in lockstep until the LAST
              member finishes (the classic static-batch bubble).
  continuous  slot-pool engine on contiguous rings: retirement frees a
              slot immediately and the queue backfills it.  Whole prompts
              load in ONE unified iteration (decode rows ride the same
              pooled forward), so a long prompt stretches that
              iteration's wall-clock for everyone sharing it.
  chunked     continuous + ``prefill_chunk``: long prompts stream in one
              fixed-size chunk per unified iteration instead, bounding
              per-iteration work, so short requests keep emitting tokens
              at decode cadence — the TTFT columns are where this shows.
              Every engine iteration is exactly one jit dispatch either
              way (the dispatches-per-iteration column pins it).
  paged       slot-pool engine on the page arena: slots own only the
              pages their tokens occupy, the arena is sized to a fraction
              of the contiguous footprint (--pages-frac), and exhaustion
              preempts the lowest-priority slot instead of deadlocking.
              Run twice: ``prefix_share=False`` (PR 2 one-owner pages)
              and ``prefix_share=True`` — the trace prepends a shared
              system prompt (--shared-prefix tokens) to every request, so
              the share run's hash-consed admission maps every slot onto
              ONE copy of those pages (prefix hit rate / peak-page-bytes
              columns).  --fused adds a third paged run decoding through
              the fused gather-decode Pallas kernel
              (repro.kernels.paged_attn) instead of materializing the
              gathered ring view; on CPU that kernel runs in interpret
              mode, so its per-iteration time is a correctness figure
              there and a perf figure only on real TPU backends.
  spec        paged+share + ``spec_decode``: a layer-truncated draft
              (--spec-draft-layers of the trunk, shared packed weights)
              proposes --spec-k tokens per slot per iteration and ONE
              pooled verify forward scores all k+1 positions — the
              accept-rate and tokens-per-verify-step columns are the
              figure of merit (on real hardware a verify step costs about
              one bandwidth-bound decode step, so tokens/step is the
              expected speedup; CPU smoke wall-clock is dispatch-bound
              and not the signal).  --spec-k 0 disables the run.
  slo         the traffic-layer run: a replayable OPEN-LOOP two-tenant
              trace (repro.serve.trace — heavy-tailed Pareto arrivals,
              gold/bronze tenant mix with per-request TTFT/TPOT SLOs,
              gold riding a shared system prompt) served paged+share
              +chunked under the quota fair-share policy with COW-aware
              preemption and SLO-adaptive chunk width.  The headline is
              goodput_under_slo — tokens from SLO-meeting requests per
              second — plus per-tenant TTFT p50/p99 and preemption
              counts (all in the --json schema; CI guards goodput).

Every run's --json record carries the FULL EngineReport schema with
nulls for features that were off, so downstream guards and diffs never
KeyError across configs.

Timing methodology: every engine first replays the SAME trace untimed —
that pass compiles the decode/chunk jits and every prefill shape the trace
will touch — then the reported window measures a second, steady-state
replay.  The warmup (≈ compile-dominated) pass is reported in its own
column instead of polluting tok/s and TTFT, which is what the previous
version of this benchmark got wrong.  TTFT per request is wall-clock from
the timed window's start to that request's first streamed token; p50/p99
summarize the trace.

Reports tokens/s, TTFT p50/p99, slot utilization, peak cache bytes and
page-arena occupancy — the memory story behind the paper's packed uint32
K/V^T caches plus the latency story chunked admission buys on top.
CPU-friendly smoke configs; pass --arch / sizes to scale up.

Run:  PYTHONPATH=src python benchmarks/serve_throughput.py
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import base
from repro.launch.runtime import deploy_params, use_compile_cache
from repro.models.lm import build_model
from repro.serve import kvcache, trace as trace_lib
from repro.serve.engine import (CacheConfig, PolicyConfig, Request,
                                ServeConfig, ServeEngine, SpecConfig)


def make_trace(rng, n, vocab, lo, hi, new_lo, new_hi, long_frac=0.25,
               shared_prefix=0):
    """Mixed short/long request trace: most requests draw uniform short
    prompts/budgets; a ``long_frac`` tail uses the top of both ranges so
    the static scheduler's bubble, the contiguous pool's stranded ring
    memory, and whole-wave prefill's TTFT stall all show.
    ``shared_prefix`` prepends one common system prompt to every request
    (the prefix-sharing workload: N slots, one copy of those pages)."""
    sys_prompt = rng.integers(0, vocab, (shared_prefix,)).astype(np.int32)
    reqs = []
    for i in range(n):
        if rng.random() < long_frac:
            plen, budget = hi, new_hi
        else:
            plen = int(rng.integers(lo, max(lo + 1, hi // 4 + 1)))
            budget = int(rng.integers(new_lo, max(new_lo + 1,
                                                  new_hi // 2 + 1)))
        toks = np.concatenate(
            [sys_prompt, rng.integers(0, vocab, (plen,)).astype(np.int32)])
        reqs.append(Request(rid=i, tokens=toks, max_new_tokens=budget))
    return reqs


def _ttft_stats(ttft):
    arr = np.asarray(sorted(ttft.values()))
    p50, p99 = np.percentile(arr, [50, 99])
    return {"ttft_p50_s": float(p50), "ttft_p99_s": float(p99)}


def run_static(eng: ServeEngine, reqs, num_slots: int):
    """Wave scheduling: pad each pool-sized wave to its longest prompt and
    decode every row to the wave's largest budget.  Only each request's own
    token budget counts as useful output — the extra lockstep steps are the
    static-batch bubble the utilization number exposes.  TTFT for a wave
    member is the wave's first decode step (prior waves included)."""
    def one_pass():
        t0 = time.perf_counter()
        produced = 0
        steps = 0
        peak_bytes = 0.0
        ttft = {}
        for i in range(0, len(reqs), num_slots):
            wave = reqs[i:i + num_slots]
            smax = max(len(r.tokens) for r in wave)
            horizon = max(r.max_new_tokens for r in wave)
            batch = np.zeros((len(wave), smax), np.int32)
            # static batching cannot mask ragged prompts -> right-align so
            # the final position is real for every row (left-pad serving)
            for j, r in enumerate(wave):
                batch[j, -len(r.tokens):] = r.tokens

            def cb(step, toks, wave=wave):
                if step == 0:
                    stamp = time.perf_counter() - t0
                    for r in wave:
                        ttft.setdefault(r.rid, stamp)

            _, report = eng.generate(batch, max_new_tokens=horizon,
                                     stream_cb=cb)
            peak_bytes = max(peak_bytes, report["total_bytes"])
            steps += horizon
            produced += sum(r.max_new_tokens for r in wave)
        return (produced, steps, peak_bytes, ttft,
                time.perf_counter() - t0)

    *_, warmup_s = one_pass()      # untimed warmup replay: compiles
    produced, steps, peak_bytes, ttft, dt = one_pass()
    util = produced / max(steps * num_slots, 1)
    # full-schema base (mostly nulls — the static path has no serve
    # loop) so every run's JSON record carries the same key set
    out = dict(kvcache.EngineReport().as_dict())
    out.update({"tokens": produced, "seconds": dt,
                "tokens_per_s": produced / dt, "slot_utilization": util,
                "peak_cache_bytes": peak_bytes, "warmup_s": warmup_s,
                **_ttft_stats(ttft)})
    return out


def run_continuous(eng: ServeEngine, reqs, engine_latency=False):
    """One warmup + one timed replay of ``reqs`` through ``eng``.

    The run dict is the engine's FULL ``EngineReport`` schema (nulls for
    features that were off) plus the benchmark-level wall-clock figures.
    ``engine_latency=False`` overrides the report's TTFT percentiles
    with window-relative stamps (every request queued at t0 — the
    closed-loop runs, comparable to ``run_static``); True keeps the
    engine's arrival-relative figures (the open-loop SLO run)."""
    t0 = time.perf_counter()
    eng.serve(reqs)                # untimed warmup replay: compiles every
    warmup_s = time.perf_counter() - t0       # shape this trace touches
    ttft = {}
    t0 = time.perf_counter()

    def cb(rid, i, tok):
        ttft.setdefault(rid, time.perf_counter() - t0)

    results, report = eng.serve(reqs, stream_cb=cb)
    dt = time.perf_counter() - t0
    produced = sum(len(v) for v in results.values())
    out = dict(report.as_dict())
    out.update({"tokens": produced, "seconds": dt,
                "tokens_per_s": produced / dt,
                # wall time per engine iteration (one pooled decode step
                # plus that iteration's admission/chunk work) — NOT
                # isolated decode-step latency
                "iter_ms": dt * 1e3 / max(report["decode_steps"], 1),
                "peak_cache_bytes": report["total_bytes"],
                "warmup_s": warmup_s})
    if not engine_latency:
        out.update(_ttft_stats(ttft))
    return out


def run_slo(model, dparams, args, cfg, max_len, max_blocks, num_pages):
    """The traffic-layer run: replay a deterministic heavy-tailed
    two-tenant open-loop trace through the quota fair-share policy
    (paged + shared prefixes + SLO-adaptive chunked prefill + COW-aware
    preemption) and report goodput under SLO."""
    tcfg = slo_trace_config(args, cfg)
    records = trace_lib.generate_trace(tcfg)
    sc = ServeConfig(
        num_slots=args.slots,
        cache=CacheConfig(max_len=max_len, paged=True,
                          page_size=args.page_size,
                          max_blocks=max_blocks, num_pages=num_pages),
        policy=PolicyConfig(kind="quota",
                            quotas={t.name: t.weight
                                    for t in tcfg.tenants},
                            prefill_chunk=args.prefill_chunk,
                            adaptive_chunk=True, cow_victims=True))
    eng = ServeEngine(model, dparams, sc)
    return run_continuous(eng, trace_lib.as_requests(records),
                          engine_latency=True)


def slo_trace_config(args, cfg) -> trace_lib.TraceConfig:
    """The benchmark's canonical two-tenant trace: gold (3x quota
    weight, tight SLOs, shared system prompt) vs bronze (1x, loose
    SLOs, cold prompts), Pareto-burst arrivals."""
    return trace_lib.TraceConfig(
        n_requests=args.slo_requests,
        arrival_rate=args.slo_rate,
        heavy_tail=args.slo_heavy_tail,
        mean_prompt=max(8, args.max_prompt // 4),
        max_prompt=args.max_prompt,
        mean_new=max(4, args.max_new // 4),
        max_new=args.max_new,
        vocab=cfg.vocab_size,
        tenants=(
            trace_lib.TenantSpec("gold", weight=3.0,
                                 ttft_slo_s=args.slo_ttft,
                                 tpot_slo_s=args.slo_tpot,
                                 system_prompt_len=args.shared_prefix),
            trace_lib.TenantSpec("bronze", weight=1.0,
                                 ttft_slo_s=4 * args.slo_ttft,
                                 tpot_slo_s=4 * args.slo_tpot)),
        seed=args.seed)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="smollm-135m")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--min-prompt", type=int, default=4)
    p.add_argument("--max-prompt", type=int, default=96)
    p.add_argument("--min-new", type=int, default=4)
    p.add_argument("--max-new", type=int, default=40)
    p.add_argument("--prefill-chunk", type=int, default=32,
                   help="chunk width for the chunked run (multiple of 32)")
    p.add_argument("--page-size", type=int, default=32)
    p.add_argument("--pages-frac", type=float, default=0.5,
                   help="paged arena size as a fraction of the fully "
                        "provisioned slots*max_blocks pool")
    p.add_argument("--shared-prefix", type=int, default=48,
                   help="shared system-prompt tokens prepended to every "
                        "request (0 disables the prefix-sharing workload)")
    p.add_argument("--fused", action="store_true",
                   help="add a paged run decoding through the fused "
                        "gather-decode Pallas kernel (interpret mode off "
                        "TPU: correctness face, not a CPU perf face)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="drafted tokens per verify step for the "
                        "speculative run (0 disables it)")
    p.add_argument("--spec-draft-layers", type=int, default=1,
                   help="depth of the layer-truncated draft (shares the "
                        "trunk's packed weights)")
    p.add_argument("--slo-requests", type=int, default=10,
                   help="requests in the open-loop SLO trace (0 disables "
                        "the slo run)")
    p.add_argument("--slo-rate", type=float, default=32.0,
                   help="mean arrivals/second for the SLO trace")
    p.add_argument("--slo-heavy-tail", type=float, default=1.5,
                   help="Pareto shape for the SLO trace's inter-arrival "
                        "bursts (must be > 1; smaller = burstier)")
    p.add_argument("--slo-ttft", type=float, default=30.0,
                   help="gold-tenant TTFT budget in seconds (bronze gets "
                        "4x; generous defaults keep CPU smoke goodput "
                        "nonzero — tighten on real hardware)")
    p.add_argument("--slo-tpot", type=float, default=10.0,
                   help="gold-tenant seconds-per-output-token budget "
                        "(bronze gets 4x)")
    p.add_argument("--autotune", action="store_true",
                   help="append a tiny fused-kernel block-size/layout "
                        "sweep (benchmarks/kernel_autotune.py) to the "
                        "report, embedded under the 'autotune' JSON key")
    p.add_argument("--json", default=None,
                   help="write the per-run result dict as JSON (the CI "
                        "bench-smoke job uploads this artifact and fails "
                        "on zero-throughput markers)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    cfg = base.get_smoke_config(args.arch)
    if cfg.skip_decode or cfg.frontend_tokens:
        raise SystemExit(f"{args.arch} has no token-only decode face")
    use_compile_cache()
    model = build_model(cfg)
    dparams = deploy_params(model, args.seed)
    max_len = args.shared_prefix + args.max_prompt + args.max_new + 8
    rng = np.random.default_rng(args.seed)
    reqs = make_trace(rng, args.requests, cfg.vocab_size,
                      args.min_prompt, args.max_prompt,
                      args.min_new, args.max_new,
                      shared_prefix=args.shared_prefix)

    max_blocks = -(-max_len // args.page_size)
    num_pages = max(max_blocks,
                    int(args.pages_frac * args.slots * max_blocks))
    plain_cache = CacheConfig(max_len=max_len)
    paged_cache = CacheConfig(max_len=max_len, paged=True,
                              page_size=args.page_size,
                              max_blocks=max_blocks, num_pages=num_pages)

    def mk(m=model, cache=plain_cache, spec=None, policy=None):
        return ServeEngine(m, dparams, ServeConfig(
            num_slots=args.slots, cache=cache, spec=spec, policy=policy))
    print(f"[{cfg.name}] {args.requests} requests x {args.slots} slots; "
          f"prompts {args.min_prompt}-{args.max_prompt} "
          f"(+{args.shared_prefix} shared system tokens), "
          f"budgets {args.min_new}-{args.max_new} (mixed short/long); "
          f"chunk={args.prefill_chunk}, page_size={args.page_size}, "
          f"arena {num_pages} pages "
          f"(vs {args.slots * max_blocks} fully provisioned)")
    runs = [("static", run_static(mk(), reqs, args.slots)),
            ("continuous", run_continuous(mk(), reqs)),
            ("chunked", run_continuous(
                mk(policy=PolicyConfig(
                    prefill_chunk=args.prefill_chunk)), reqs)),
            ("paged", run_continuous(
                mk(cache=dataclasses.replace(paged_cache,
                                             prefix_share=False)), reqs)),
            ("paged+share", run_continuous(mk(cache=paged_cache), reqs))]
    if args.fused:
        cfg_k = cfg.with_(binary=dataclasses.replace(cfg.binary,
                                                     paged_kernel=True))
        runs.append(("paged+fused", run_continuous(
            mk(m=build_model(cfg_k), cache=paged_cache), reqs)))
    if args.spec_k > 0:
        runs.append(("paged+share+spec", run_continuous(
            mk(cache=paged_cache,
               spec=SpecConfig(k=args.spec_k,
                               draft_layers=args.spec_draft_layers)),
            reqs)))
    if args.slo_requests > 0:
        runs.append(("slo", run_slo(model, dparams, args, cfg, max_len,
                                    max_blocks, num_pages)))
    for name, r in runs:
        extra = ""
        if r.get("page_utilization") is not None:
            ppu = r["peak_page_utilization"] * 100
            hit = r["prefix_hit_rate"] * 100
            extra = (f"  peak-page-util {ppu:4.0f}%  "
                     f"peak pages {r['peak_page_bytes'] / 1024:6.1f} KiB  "
                     f"hit {hit:3.0f}%  cow {r['cow_copies']:.0f}  "
                     f"preempt {r['preemptions']:.0f}")
        if r.get("spec_accept_rate") is not None:
            extra += (f"  accept {r['spec_accept_rate'] * 100:3.0f}%  "
                      f"{r['spec_tokens_per_step']:.2f} tok/verify-step  "
                      f"rollback-frees {r['pages_freed_rollback']:.0f}")
        if r.get("goodput_under_slo") is not None and name == "slo":
            extra += (f"  goodput {r['goodput_under_slo']:6.1f} tok/s  "
                      f"slo-met {r['slo_attainment'] * 100:3.0f}%")
        step = (f"  iter {r['iter_ms']:6.1f}ms"
                if r.get("iter_ms") is not None else "")
        if r.get("dispatches_per_iteration") is not None:
            step += (f"  {r['dispatches_per_iteration']:.0f} disp/iter  "
                     f"{r['engine_compiles']:.0f} compiles")
        print(f"  {name:11s} {r['tokens']:5d} tok  {r['seconds']:6.2f}s "
              f"(+{r['warmup_s']:5.2f}s warmup)  "
              f"{r['tokens_per_s']:7.1f} tok/s  "
              f"ttft p50 {r['ttft_p50_s'] * 1e3:7.1f}ms "
              f"p99 {r['ttft_p99_s'] * 1e3:7.1f}ms  "
              f"util {r['slot_utilization'] * 100:5.1f}%  "
              f"peak cache {r['peak_cache_bytes'] / 1024:8.1f} KiB"
              f"{step}{extra}")
    by_name = {name: r for name, r in runs}
    static, cont = by_name["static"], by_name["continuous"]
    chunked, paged = by_name["chunked"], by_name["paged"]
    share = by_name["paged+share"]
    speedup = cont["tokens_per_s"] / max(static["tokens_per_s"], 1e-9)
    saving = 1 - paged["peak_cache_bytes"] / max(cont["peak_cache_bytes"], 1)
    ratio = paged["peak_cache_bytes"] / max(cont["peak_cache_bytes"], 1)
    t50 = cont["ttft_p50_s"] / max(chunked["ttft_p50_s"], 1e-9)
    t99 = cont["ttft_p99_s"] / max(chunked["ttft_p99_s"], 1e-9)
    thr = chunked["tokens_per_s"] / max(cont["tokens_per_s"], 1e-9)
    print(f"  continuous/static throughput: {speedup:.2f}x")
    print(f"  chunked/continuous: ttft p50 {t50:.2f}x faster, "
          f"p99 {t99:.2f}x faster, throughput {thr:.2f}x")
    print(f"  paged/continuous peak cache bytes: {ratio:.2f}x "
          f"({saving * 100:.0f}% saved)")
    pratio = share["peak_page_bytes"] / max(paged["peak_page_bytes"], 1)
    print(f"  share/paged peak page bytes: {pratio:.2f}x "
          f"({(1 - pratio) * 100:.0f}% saved; prefix hit rate "
          f"{share['prefix_hit_rate'] * 100:.0f}%, "
          f"{share['cow_copies']:.0f} cow copies)")
    if "paged+fused" in by_name:
        fused = by_name["paged+fused"]
        print(f"  fused/gather serve iteration: {fused['iter_ms']:.1f}ms vs "
              f"{share['iter_ms']:.1f}ms "
              f"({'interpret-mode CPU — correctness face only' if jax.default_backend() != 'tpu' else 'TPU'})")
    if "paged+share+spec" in by_name:
        sp = by_name["paged+share+spec"]
        print(f"  speculative (k={args.spec_k}, "
              f"{args.spec_draft_layers}-layer draft): "
              f"accept rate {sp['spec_accept_rate'] * 100:.0f}%, "
              f"{sp['spec_tokens_per_step']:.2f} tokens/verify-step over "
              f"{sp['spec_steps']:.0f} steps "
              f"(amortizes per-step weight+cache traffic by the same "
              f"factor on bandwidth-bound hardware)")
    if "slo" in by_name:
        sl = by_name["slo"]
        tens = sl.get("tenants") or {}
        per = "; ".join(
            f"{t}: p99 ttft {v['ttft_p99_s'] * 1e3:.0f}ms, "
            f"{v['preemptions']:.0f} preempt"
            for t, v in sorted(tens.items())
            if v.get("ttft_p99_s") is not None)
        print(f"  slo trace (quota policy, heavy-tail "
              f"{args.slo_heavy_tail}): goodput under SLO "
              f"{sl['goodput_under_slo']:.1f} tok/s, "
              f"{sl['slo_attainment'] * 100:.0f}% of requests in SLO "
              f"({per})")

    def jsonable(v):
        if isinstance(v, dict):
            return {k: jsonable(x) for k, x in v.items()}
        if v is None or isinstance(v, (bool, str)):
            return v
        if isinstance(v, (list, tuple)):
            return [jsonable(x) for x in v]
        return float(v)

    report = {name: jsonable(r) for name, r in by_name.items()}
    if args.autotune:
        import kernel_autotune
        sweep = kernel_autotune.autotune_sps(
            h=cfg.num_heads, l=96, d_h=cfg.resolved_head_dim,
            blocks=kernel_autotune.TINY_BLOCKS, iters=2, seed=args.seed)
        best = sweep["best"]
        if best is None:
            raise SystemExit("autotune: no config matched the oracle")
        print(f"  autotune best ({len(sweep['sweep'])} configs): "
              f"{best['path']} bq={best['bq']} bk={best['bk']} "
              f"({best['step_ms']:.2f} ms/step"
              f"{', interpret mode' if sweep['interpret'] else ''})")
        report["autotune"] = sweep
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"  wrote {args.json}")
    return by_name


if __name__ == "__main__":
    main()
