#!/usr/bin/env python3
"""The benchmark's one command.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` (one model configuration under one
traffic mix) on the chip this process finds, and prints one JSON line
as the last line of standard output:

  {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
   "device": {...}, ["breakdown": {...},] "run_s": ..., "engine": {...},
   "checks": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (read from a profiler trace of the
window).  ``engine`` holds the engine's own counts of the serve call
(iterations, speculative verify steps, drafts accepted).  ``checks``
holds every number the correctness comparison read, each beside its
limit; the same numbers are the last lines of standard error.

Everything is found by name:
  bench/configs/<config>.json    model sizes, source, reference sizes
  bench/traffic/<traffic>.json   the mix: arrivals, lengths, engine shape,
                                 and the module that serves it ("kind")
  bench/cells/<workload>.json    the cell's correctness limit and sample
  bench/drivers/<kind>.py        runs the system under test for a mix kind
  bench/metrics/<metric>.py      one reader per metric name
  bench/checks/<check>.py        the comparison that decides ``correct``

The process exits non-zero, and prints no result, when JAX finds no TPU
or fewer chips than the cell asks for, when ``REPRO_FORCE_INTERPRET`` is
set, or when the program (``src/``) is not beside this directory.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold '-')."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench, workload, trace):
    """Metric entries this cell reports: end-to-end ones without tracing,
    per-layer ones with it (a metric with a ``workloads`` key only in
    those cells)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def fail(msg: str, code: int = 3):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def parser(doc: str) -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=doc.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def context(args):
    """Load the cell's files, check the chips and the program, turn on
    the compile cache; returns the ``Context`` a ``bench/drivers``
    module takes."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        fail(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
             2)
    wl = cells[args.workload]
    cfg = load_json(BENCH, "configs", wl["config"] + ".json")
    mix = load_json(BENCH, "traffic", wl["traffic"] + ".json")
    cell = load_json(BENCH, "cells", wl["name"] + ".json")

    if os.environ.get("REPRO_FORCE_INTERPRET") is not None:
        fail("REPRO_FORCE_INTERPRET is set: the benchmark measures kernels "
             "only as the chip compiles them")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the program is not beside the benchmark ({src} missing)")
    sys.path.insert(0, src)
    sys.path.insert(0, ROOT)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {len(devices)} {devices[0].platform} "
             f"device(s)")
    if len(devices) < wl["chips"]:
        fail(f"the cell needs {wl['chips']} chips, JAX found "
             f"{len(devices)}")

    from bench import common

    common.use_compile_cache(ROOT)
    return common.Context(
        args=args, bench=bench, workload=wl, cfg=cfg, mix=mix, cell=cell,
        devices=devices[:wl["chips"]], t_start=T_START, bench_dir=BENCH,
        root=ROOT)


def main(argv=None) -> int:
    args = parser(__doc__).parse_args(argv)
    ctx = context(args)
    bench, devices = ctx.bench, ctx.devices
    run = load_module("drivers", ctx.mix["kind"]).run(ctx)
    cell = ctx.cell

    metrics = {}
    for m in cell_metrics(bench, args.workload, args.trace):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    if args.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]

    check = load_module("checks", cell["check"]["name"])
    verdict = check.check(run)
    result = {"correct": verdict["correct"],
              "attempted": run.attempted,
              "failed": run.unfinished + verdict["failed"],
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = run.trace["breakdown"]
    result["run_s"] = time.perf_counter() - T_START
    result["engine"] = run.engine
    result["checks"] = verdict["numbers"]
    for name, n in verdict["numbers"].items():
        print(f"check {name}: {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
