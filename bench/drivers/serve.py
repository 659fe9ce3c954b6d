"""Serving driver: one mix served open loop through ``ServeEngine.serve``.

Set-up builds the registry model, makes its deploy weights on the chip
in one jitted call from the seed, and serves a short warm-up trace that
touches only the cell's two programs (the pooled chunk step at the mix's
chunk width and the pooled decode step), with the pool at the mix's
slot count so every shape the window uses is compiled or loaded.

The window is the first ``--seconds`` of ``serve()``'s clock: every
request of the run is due inside it (``bench/gen.py``), and ``serve()``
runs on until all of them are done, so their latencies are whole and
their outputs can be checked.  Each emitted token is stamped with the
host clock in the stream callback, which the engine calls after the
host has synced on the step that made the token.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import time

import numpy as np

from bench import common, gen, trace_reduce


def _model_config(cfg):
    """The registry configuration, checked against the sizes the
    configuration file states (and the reference runs)."""
    from repro.configs import base

    mc = base.get_config(cfg["registry_id"])
    r = cfg["reference"]
    have = {"family": mc.family, "num_layers": mc.num_layers,
            "d_model": mc.d_model, "num_heads": mc.num_heads,
            "num_kv_heads": mc.num_kv_heads,
            "head_dim": mc.resolved_head_dim, "d_ff": mc.d_ff,
            "vocab_size": mc.vocab_size, "window": mc.window_size,
            "rope_theta": mc.rope_theta,
            "tie_embeddings": mc.tie_embeddings,
            "compute_dtype": mc.compute_dtype}
    want = dict(r, compute_dtype="bfloat16")
    bad = {k: (have[k], want[k]) for k in have
           if k in want and have[k] != want[k]}
    if bad:
        raise ValueError(f"registry {cfg['registry_id']!r} differs from "
                         f"its configuration file: {bad}")
    return mc


def capacity(mix) -> int:
    """Per-slot cache capacity: the mix's longest request, in whole
    pages."""
    page = mix["engine"]["page_size"]
    return -(-gen.longest_request(mix) // page) * page


def _serve_config(mix):
    from repro.serve.engine import (CacheConfig, PolicyConfig, ServeConfig,
                                    SpecConfig)

    e = mix["engine"]
    spec = SpecConfig(k=e["spec_k"], draft_layers=e["spec_draft_layers"]) \
        if e.get("spec_k") else SpecConfig()
    return ServeConfig(
        sampler="greedy", num_slots=e["num_slots"],
        cache=CacheConfig(max_len=capacity(mix), paged=True,
                          page_size=e["page_size"],
                          prefix_share=e["prefix_share"]),
        policy=PolicyConfig(prefill_chunk=e["prefill_chunk"]), spec=spec)


def _warmup_requests(mix, vocab, seed):
    """One request per slot, each a prompt one token longer than the
    chunk (two chunk steps) and two output tokens (then decode steps)."""
    from repro.serve.engine import Request

    e = mix["engine"]
    rng = np.random.default_rng([seed, 1])
    return [Request(rid=i, tokens=rng.integers(
        0, vocab, e["prefill_chunk"] + 1).astype(np.int32),
        max_new_tokens=2) for i in range(e["num_slots"])]


TRACE_S = 10.0   # seconds of the window a --trace 1 run profiles


class _Traced:
    """Profiles the first ``TRACE_S`` seconds of the window inside a host
    annotation that marks them; the stream callback ends the profile
    (``stop``), or leaving the block does."""

    def __init__(self, trace_dir):
        self.trace_dir = trace_dir
        self.ann = None

    def __enter__(self):
        import jax

        if self.trace_dir:
            # no Python function tracer: it would slow the host loop
            # that the idle share measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self.ann.__enter__()
        return self

    def stop(self):
        import jax

        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
            jax.profiler.stop_trace()

    def __exit__(self, *exc):
        self.stop()


def make_weights(model, seed: int, embed_scale: float):
    """Deploy weights from the seed in one jitted call on the chip: the
    program's own init and convert, then the embedding table scaled by
    ``embed_scale`` (the configuration file's weight recipe)."""
    import jax

    def build(key):
        p = dict(model.convert(model.init(key)))
        p["embed"] = dict(p["embed"],
                          embedding=p["embed"]["embedding"] * embed_scale)
        return p

    return jax.block_until_ready(jax.jit(build)(jax.random.PRNGKey(seed)))


def swap_weights(eng, seed: int, embed_scale: float) -> None:
    """Give a built engine the deploy weights of another seed, and its
    self-speculative draft the new weights' first layers; the shapes
    stay, so nothing compiles again."""
    eng.dparams = None
    eng.dparams = make_weights(eng.model, seed, embed_scale)
    if eng.draft_dparams is not None:
        _, eng.draft_dparams = eng.model.truncate_deploy(
            eng.dparams, eng.draft_model.cfg.num_layers)


def engine_numbers(counters):
    """The engine's own counts of the serve call (window and drain):
    iterations, how many were speculative verify steps and their share,
    drafts accepted, tokens a verify step emitted per busy slot."""
    its = counters.get("iterations") or 0
    spec = counters.get("spec_steps") or 0
    return {"iterations": its,
            "dispatches_per_iteration":
                counters.get("dispatches_per_iteration"),
            "preemptions": counters.get("preemptions"),
            "verify_steps": spec,
            "verify_share": spec / its if its else None,
            "spec_accept_rate": counters.get("spec_accept_rate"),
            "spec_tokens_per_step": counters.get("spec_tokens_per_step")}


def setup(ctx: common.Context):
    """Model, deploy weights from the seed, and a warmed engine.  Prints
    where the set-up time went (seconds, compiles, cache hits) to
    standard error."""
    from repro.models.lm import build_model
    from repro.serve.engine import ServeEngine

    t0 = time.perf_counter()
    with common.CompileClock() as clock:
        model = build_model(_model_config(ctx.cfg))
        dparams = make_weights(model, ctx.args.seed,
                               ctx.cfg["reference"]["embed_scale"])
        t1 = time.perf_counter()
        eng = ServeEngine(model, dparams, _serve_config(ctx.mix))
        eng.serve(_warmup_requests(ctx.mix,
                                   ctx.cfg["reference"]["vocab_size"],
                                   ctx.args.seed))
        t2 = time.perf_counter()
    print(json.dumps({"setup": {
        "imports_s": t0 - ctx.t_start, "weights_s": t1 - t0,
        "warmup_s": t2 - t1, **clock.between(t0, t2)}}),
        file=sys.stderr, flush=True)
    return eng


def serve_window(eng, reqs, seconds: float, trace_dir=None):
    """Serve ``reqs`` open loop; returns (served tokens by rid, token
    stamps by rid, window start, serve end, engine counters, compiles in
    the window)."""
    from repro.serve.engine import Request

    requests = [Request(rid=r.rid, tokens=r.prompt,
                        max_new_tokens=r.max_new_tokens, arrival_s=r.due_s)
                for r in reqs]
    stamps = [[] for _ in reqs]
    traced = _Traced(trace_dir)
    trace_end = [float("inf")]

    def on_token(rid, index, token):
        now = time.perf_counter()
        stamps[rid].append(now)
        if now >= trace_end[0]:
            traced.stop()

    with common.CompileClock() as clock, traced:
        t_call = time.perf_counter()
        trace_end[0] = t_call + min(TRACE_S, seconds)
        out, report = eng.serve(requests, stream_cb=on_token)
        t_done = time.perf_counter()
    counters = {f.name: getattr(report, f.name)
                for f in dataclasses.fields(report)}
    served = {r.rid: np.asarray(out.get(r.rid, ()), np.int32) for r in reqs}
    return (served, stamps, t_call, t_done, counters,
            clock.between(t_call, t_call + seconds))


def host_numbers(reqs, served, stamps, t_call, t_done, counters,
                 seconds: float):
    t_end = t_call + seconds
    return {
        "seconds": seconds,
        "ttft_ms": [(s[0] - (t_call + r.due_s)) * 1e3
                    for r, s in zip(reqs, stamps) if s],
        "itl_ms": [d * 1e3 for s in stamps for d in np.diff(s)],
        "output_tokens_in_window": sum(1 for s in stamps for t in s
                                       if t < t_end),
        "drain_s": t_done - t_end,
        # the engine's clock starts after it has checked the requests;
        # its offset from the window's start is how late the schedule's
        # origin ran (per-request admission lag needs spans in the
        # program)
        "origin_lag_ms": (t_done - counters["elapsed_s"] - t_call) * 1e3,
        "unfinished": sum(1 for r in reqs
                          if len(served[r.rid]) < r.max_new_tokens),
    }


def run(ctx: common.Context) -> common.Run:
    args = ctx.args
    seconds = float(args.seconds)
    eng = setup(ctx)
    reqs = gen.generate(ctx.mix, args.seed, seconds,
                        ctx.cfg["reference"]["vocab_size"])
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ctx.bench_dir, ".cache", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    served, stamps, t_call, t_done, counters, win = serve_window(
        eng, reqs, seconds, trace_dir)
    peak = common.peak_bytes(ctx.devices[0])
    del eng
    gc.collect()

    host = host_numbers(reqs, served, stamps, t_call, t_done, counters,
                        seconds)
    host.update(setup_s=t_call - ctx.t_start,
                window_compiles=win["compiles"],
                window_compile_s=win["compile_s"])
    print(json.dumps({"run": {
        "workload": ctx.workload["name"], "seed": args.seed,
        "requests": len(reqs), "setup_s": host["setup_s"],
        "origin_lag_ms": host["origin_lag_ms"],
        "window_compiles": win["compiles"], "drain_s": host["drain_s"],
        "output_tokens_in_window": host["output_tokens_in_window"],
        "unfinished": host["unfinished"],
        **engine_numbers(counters),
        "memory_peak_bytes": peak}}), file=sys.stderr, flush=True)

    tr = None
    if trace_dir:
        tr = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = common.Run(
        ctx=ctx, host=host, counters=counters, attempted=len(reqs),
        unfinished=host["unfinished"], memory_peak_bytes=peak, trace=tr,
        engine=engine_numbers(counters))
    run.check = {"requests": reqs, "served": served, "stamps": stamps,
                 "t_call": t_call}
    return run
