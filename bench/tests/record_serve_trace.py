"""Record the ``data/v5e_serve_trace`` fixture on a TPU:

  python3 -m bench.tests.record_serve_trace bench/tests/data/v5e_serve_trace

One tiny run of the chat cell's driver (``bench/drivers/serve.py``),
profiled as a ``--trace 1`` run profiles a cell: Python tracer off, the
window inside the ``bench_window`` annotation.  The window lasts as long
as the requests' arrivals, so on a v5e the whole ``serve()`` call fits
in the profile and the driver stops it when the call returns.  To keep
the fixture small, ``trim`` then drops what no reducer reads (the
programs' HLO and the device ops' metadata stats) and the perfetto
export is deleted.  The model and mix are ``bench/tests/tiny.py``'s cuts
of the cell (here one layer of width 128; pages and chunks of 32, 4
slots, k=4 speculation), with the requests due in the first 0.15 s and
every shared prompt the same, so that later requests adopt its pages.
``expected.json`` holds counts read straight from the trace's planes,
for the reducer tests to compare with: the ``jit_step`` executions that
started in the window and the ``serve.dispatch.*`` spans per program.
Exits non-zero off a TPU.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import shutil
import sys
import time

from bench import common, gen, run as harness, trace_reduce
from bench.tests import tiny

WORKLOAD = "granite-3-2b.chat-shared-spec4"
SEED = 2**31 + 14
SECONDS = 0.15     # the requests are due in the first SECONDS
RATE = 40.0
SIZE = dict(tiny.SMALL, num_layers=1)


def plane_counts(trace_dir: str) -> dict:
    """Counts read from the planes without the reducers: the window, the
    ``jit_step`` executions starting in it, the dispatch spans in it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    host = [(ln.name, ev) for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events]
    w0, = [ev.start_ns for _, ev in host if ev.name == trace_reduce.WINDOW]
    w1, = [ev.start_ns + ev.duration_ns for _, ev in host
           if ev.name == trace_reduce.WINDOW]
    dispatch = collections.Counter(
        ev.name.rsplit(".", 1)[1] for _, ev in host
        if ev.name.startswith("serve.dispatch.") and w0 <= ev.start_ns < w1)
    steps = sum(1 for p in pd.planes if p.name.startswith("/device:TPU")
                for ln in p.lines if ln.name == trace_reduce.MODULES_LINE
                for ev in ln.events
                if ev.name.startswith("jit_step(") and w0 <= ev.start_ns < w1)
    return {"window_ns": int(w1 - w0), "jit_step_executions": steps,
            "dispatch_spans": dict(sorted(dispatch.items())),
            "span_lines": sorted({n for n, ev in host
                                  if ev.name.startswith("serve.")})}


def _xspace():
    """The parts of the profiler's ``XSpace`` message
    (tsl/profiler/protobuf/xplane.proto) that ``trim`` edits, by their
    field numbers; every other field passes through as an unknown field.
    No installed package ships the compiled schema."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    f = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="xspace_trim.proto",
                                             package="trim", syntax="proto3")
    for name, fields in (
            ("XSpace", [("planes", 1, f.TYPE_MESSAGE, ".trim.XPlane")]),
            ("XPlane", [("name", 2, f.TYPE_STRING, None),
                        ("event_metadata", 4, f.TYPE_MESSAGE,
                         ".trim.MetadataEntry")]),
            ("MetadataEntry", [("key", 1, f.TYPE_INT64, None),
                               ("value", 2, f.TYPE_MESSAGE,
                                ".trim.XEventMetadata")]),
            ("XEventMetadata", [("stats", 5, f.TYPE_BYTES, None)])):
        m = fdp.message_type.add(name=name)
        for fname, number, ftype, tname in fields:
            repeated = fname in ("planes", "event_metadata", "stats")
            fd = m.field.add(name=fname, number=number, type=ftype,
                             label=f.LABEL_REPEATED if repeated
                             else f.LABEL_OPTIONAL)
            if tname:
                fd.type_name = tname
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("trim.XSpace"))


def trim(path: str) -> None:
    """Drop, in place, what no reducer reads: the event metadata of the
    "/host:metadata" plane (the programs' HLO) and the metadata stats of
    the device planes' events (source stacks, shapes, op names).  Every
    event keeps its name, times and stats as ``ProfileData`` reads them."""
    space = _xspace()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    for plane in space.planes:
        if plane.name == "/host:metadata":
            del plane.event_metadata[:]
        elif plane.name.startswith("/device:"):
            for entry in plane.event_metadata:
                del entry.value.stats[:]
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())


def record(out_dir: str) -> dict:
    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_serve_trace: needs a TPU")
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}[WORKLOAD]
    cfg = tiny.tiny_cfg(harness.load_json(harness.BENCH, "configs",
                                          wl["config"] + ".json"), SIZE)
    mix = tiny.tiny_mix(harness.load_json(harness.BENCH, "traffic",
                                          wl["traffic"] + ".json"))
    mix["rate_per_s"] = RATE
    mix["shared_prefix"]["prompts"] = 1
    args = argparse.Namespace(workload=WORKLOAD, seed=SEED, seconds=SECONDS,
                              trace=1)
    ctx = common.Context(
        args=args, bench=bench, workload=wl, cfg=cfg, mix=mix,
        cell=harness.load_json(harness.BENCH, "cells", WORKLOAD + ".json"),
        devices=jax.devices()[:1], t_start=time.perf_counter(),
        bench_dir=harness.BENCH, root=harness.ROOT)
    drv = harness.load_module("drivers", mix["kind"])
    shutil.rmtree(out_dir, ignore_errors=True)
    with tiny.registry_override(cfg, SIZE):
        eng = drv.setup(ctx)
        reqs = gen.generate(mix, SEED, SECONDS,
                            cfg["reference"]["vocab_size"])
        *_, counters, _ = drv.serve_window(eng, reqs, SECONDS, out_dir)
    for f in glob.glob(os.path.join(out_dir, "**", "*.trace.json.gz"),
                       recursive=True):
        os.remove(f)
    trim(trace_reduce.find_xplane(out_dir))
    want = plane_counts(out_dir)
    want["device_kind"] = jax.devices()[0].device_kind
    want["requests"] = len(reqs)
    want["engine"] = drv.engine_numbers(counters)
    with open(os.path.join(out_dir, "expected.json"), "w") as f:
        json.dump(want, f, indent=1, sort_keys=True)
        f.write("\n")
    return want


if __name__ == "__main__":
    print(json.dumps(record(sys.argv[1])))
