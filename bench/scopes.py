"""Device time per named scope in each engine program, from a profile.

The model's ops carry named scopes in their op metadata: ``sps_score``
and ``sps_context`` on the two SPS attention products, ``paged_gather``
on the block-table gathers, ``rbmm_popcount`` / ``rbmm_mxu`` on the path
each binary contraction took.  ``ProfileData`` gives an op event only
its offset and duration; the profiler's perfetto export
(``*.trace.json.gz`` beside the ``.xplane.pb``) gives each op event its
metadata's ``tf_op`` (``jit(step)/.../sps_score/rbmm_mxu/...``) in
``args``.  ``reduce_dir`` reads both:

  * the program of each ``jit_step`` execution, from the xplane, as
    ``spans.reduce`` names it: the latest ``serve.dispatch.<program>``
    span that started before the runtime enqueued the execution
    (matched by ``run_id``);
  * from the perfetto export, each device's "XLA Ops" events, each with
    its self time (its duration less that of the ops nested in it), and
    the execution ("XLA Modules" event) that holds it.  A device's
    executions pair with the xplane's by their order on that device.

and returns, per program,

  {"count": executions, "seconds": op self time,
   "scopes": {scope: op self time of the ops whose tf_op holds it}}

over every execution in the profile.  A scope's share of a program is
its seconds over the program's.  An op whose ``tf_op`` names two
scopes (``sps_score`` and ``rbmm_mxu``) counts in both.

Run a cell with it (a ``--trace 1`` run of ``bench/run.py`` with the
span reduction of ``spans.py``, that also prints ``scopes`` and each
scope's share to standard error):

  python3 bench/scopes.py --workload <name> --seed <n> --seconds <s>
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import sys
from typing import Dict, List

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import spans, trace_reduce as tr  # noqa: E402

# a device's process name in the perfetto export holds its plane's name
DEVICE = re.compile(r"/device:TPU:\d+")
SCOPES = ("sps_score", "sps_context", "paged_gather", "rbmm_popcount",
          "rbmm_mxu")


def find_perfetto(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .trace.json.gz under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir: str) -> Dict[str, Dict]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(tr.find_xplane(trace_dir))
    with gzip.open(find_perfetto(trace_dir), "rt") as f:
        events = json.load(f)["traceEvents"]
    return scope_times(events, programs_by_device(pd))


def programs_by_device(pd) -> Dict[str, List[str]]:
    """Per device plane, the program of each ``jit_step`` execution in
    start order ("unknown" before the first dispatch span)."""
    dispatch, enqueued, steps = [], {}, {}
    for plane in pd.planes:
        device = tr.DEVICE_PLANE.match(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != tr.MODULES_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                if device and ev.name.startswith(spans.STEP):
                    steps.setdefault(plane.name, []).append(
                        (s, dict(ev.stats).get("run_id")))
                elif ev.name.startswith(spans.DISPATCH):
                    dispatch.append((s, ev.name[len(spans.DISPATCH):]))
                elif ev.name == spans.ENQUEUE:
                    run = dict(ev.stats).get("run_id")
                    if run is not None:
                        enqueued[run] = min(s, enqueued.get(run, s))
    dispatch.sort()
    starts = [s for s, _ in dispatch]
    lead = [enqueued[run] - s for ex in steps.values() for s, run in ex
            if run in enqueued]
    offset = max(0, max(lead, default=0))
    out = {}
    for name, ex in steps.items():
        progs = []
        for s, run in sorted(ex):
            i = bisect.bisect_right(starts, enqueued.get(run, s + offset)) - 1
            progs.append(dispatch[i][1] if i >= 0 else "unknown")
        out[name] = progs
    return out


def _threads(events):
    """{pid: process name} and {(pid, tid): thread name}."""
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("name") == "thread_name":
            threads[(ev["pid"], ev["tid"])] = ev["args"]["name"]
    return procs, threads


def _self_times(ops):
    """(start, end, tf_op, self) for ops on one line: each op's
    duration less that of the ops directly nested in it."""
    out, stack = [], []
    for s, e, op in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, op, e - s]
        if stack:
            stack[-1][3] -= e - s
        out.append(rec)
        stack.append(rec)
    return out


def scope_times(events, programs: Dict[str, List[str]]) -> Dict[str, Dict]:
    """Per program: executions, op self seconds, and op self seconds
    per scope, from perfetto ``traceEvents`` and the programs of each
    device's executions (``programs_by_device``)."""
    procs, threads = _threads(events)
    modules, ops = {}, {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in procs:
            continue
        if not DEVICE.search(procs[ev["pid"]]):
            continue
        line = threads.get((ev["pid"], ev.get("tid")))
        s, e = float(ev["ts"]), float(ev["ts"]) + float(ev.get("dur", 0))
        if line == tr.MODULES_LINE and ev["name"].startswith(spans.STEP):
            modules.setdefault(ev["pid"], []).append((s, e))
        elif line == tr.OPS_LINE:
            ops.setdefault(ev["pid"], []).append(
                (s, e, (ev.get("args") or {}).get("tf_op", "")))
    pids = sorted(modules, key=lambda p: procs[p])
    names = sorted(programs)
    if len(pids) != len(names):
        raise ValueError(f"{len(pids)} devices with executions in the "
                         f"perfetto export, {len(names)} in the xplane")
    out: Dict[str, Dict] = {}
    for pid, name in zip(pids, names):
        mods = sorted(modules[pid])
        if len(mods) != len(programs[name]):
            raise ValueError(f"{procs[pid]}: {len(mods)} executions in the "
                             f"perfetto export, {len(programs[name])} in "
                             f"the xplane")
        starts = [s for s, _ in mods]
        for (s, e), prog in zip(mods, programs[name]):
            p = out.setdefault(prog, {"count": 0, "seconds": 0.0,
                                      "scopes": dict.fromkeys(SCOPES, 0.0)})
            p["count"] += 1
        for s, e, op, self_us in _self_times(ops.get(pid, [])):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue
            p = out[programs[name][i]]
            p["seconds"] += self_us * 1e-6
            parts = op.split("/")
            for scope in SCOPES:
                if scope in parts:
                    p["scopes"][scope] += self_us * 1e-6
    return out


def shares(scopes: Dict[str, Dict]) -> Dict[str, Dict[str, float]]:
    """Each scope's percentage of its program's op self time."""
    return {prog: {k: 100.0 * v / p["seconds"] if p["seconds"] else None
                   for k, v in p["scopes"].items()}
            for prog, p in scopes.items()}


def main(argv=None) -> int:
    from bench import run as harness

    def reduce_and_print(trace_dir):
        t = spans.reduce_dir(trace_dir)
        out = {"programs": t.get("programs"), "readings": spans.readings(t)}
        try:
            s = reduce_dir(trace_dir)
            out.update(scopes=s, scope_shares=shares(s))
        except (OSError, ValueError, KeyError) as e:
            out["scopes_error"] = repr(e)
        print(json.dumps(out), file=sys.stderr, flush=True)
        return t

    tr.reduce_dir = reduce_and_print
    args = list(sys.argv[1:] if argv is None else argv)
    return harness.main(args + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
