"""Drive a whole benchmark run on the CPU at a tiny size.

Used by the tests beside it and as the CPU rehearsal of a cell:

  JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m bench.tests.tiny <workload>

It skips the harness's look for a chip, shrinks the configuration (two
layers of width 128) and the mix (short prompts and outputs, pages and
chunks of 32, a few slots), and runs the cell's driver, its metrics and
its correctness check exactly as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
from unittest import mock

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, run as harness  # noqa: E402

SMALL = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
             d_ff=256, vocab_size=300)


def tiny_cfg(cfg, size=SMALL):
    cfg = copy.deepcopy(cfg)
    r = cfg["reference"]
    r.update(size, head_dim=size["d_model"] // size["num_heads"])
    return cfg


def tiny_mix(mix):
    mix = copy.deepcopy(mix)
    mix["prompt"]["max"] = min(mix["prompt"]["max"], 96)
    if "mean" in mix["prompt"]:
        mix["prompt"]["mean"] = min(mix["prompt"]["mean"], 32)
    out = mix["output"]
    out["max"] = min(out["max"], 24)
    out["min"] = min(out.get("min", 1), 8)
    if "mean" in out:
        out["mean"] = min(out["mean"], 8)
    if "shared_prefix" in mix:
        mix["shared_prefix"]["tokens"] = 64
    mix["engine"].update(num_slots=min(mix["engine"]["num_slots"], 4),
                         page_size=32, prefill_chunk=32)
    mix["rate_per_s"] = 4.0
    return mix


def registry_override(cfg, size=SMALL):
    """Make the registry hand out the tiny model for ``cfg``."""
    from repro.configs import base

    real = base.get_config

    def small(arch_id):
        mc = real(arch_id)
        if arch_id != cfg["registry_id"]:
            return mc
        return mc.with_(**size)

    return mock.patch.object(base, "get_config", small)


def run_cell(workload: str, seed: int = 2**31 + 3, seconds: float = 3.0,
             trace: int = 0, cell_override=None, size=SMALL):
    """One tiny run of ``workload``; returns (result dict, Run)."""
    import jax

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}[workload]
    cfg = tiny_cfg(harness.load_json(BENCH, "configs", wl["config"] + ".json"),
                   size)
    mix = tiny_mix(harness.load_json(BENCH, "traffic",
                                     wl["traffic"] + ".json"))
    cell = harness.load_json(BENCH, "cells", workload + ".json")
    if cell_override:
        cell = dict(cell, check=dict(cell["check"], **cell_override))
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    ctx = common.Context(args=args, bench=bench, workload=wl, cfg=cfg,
                         mix=mix, cell=cell, devices=jax.devices()[:1],
                         t_start=time.perf_counter(), bench_dir=BENCH,
                         root=ROOT)
    with registry_override(cfg, size):
        run = harness.load_module("drivers", mix["kind"]).run(ctx)
    metrics = {}
    for m in harness.cell_metrics(bench, workload, trace):
        v = harness.load_module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = v
    verdict = harness.load_module("checks", cell["check"]["name"]).check(run)
    return {"correct": verdict["correct"], "failed": verdict["failed"],
            "attempted": run.attempted, "metrics": metrics,
            "checks": verdict["numbers"]}, run


if __name__ == "__main__":
    res, _ = run_cell(sys.argv[1])
    print(json.dumps(res))
