"""The program's own spans in a profiler trace of one window.

``ServeEngine.serve`` opens ``serve.*`` spans (``jax.profiler``
annotations) around each phase of an iteration.  ``trace_reduce.reduce``
takes host events only from the profiler line named "python", which only
the Python function tracer fills; the benchmark runs with that tracer
off, and an annotation then lands on the line of the thread that opened
it (named after the main thread, "python3" under ``python3``).
``reduce`` here takes ``serve.*`` events from every ``/host:`` line and
returns ``trace_reduce.reduce``'s dict with, where the window holds such
events:

  spans      per span name: the spans that overlap the window, ``count``
             and ``seconds`` (clipped to the window)
  programs   per engine program (``chunk``, ``verify``, ``decode``): the
             ``count`` and device ``seconds`` of the ``jit_step``
             executions that started in the window, each attributed to
             the latest ``serve.dispatch.<program>`` span that started
             before the runtime enqueued it.  The engine reads a step's
             outputs back before it dispatches again, so execution k is
             enqueued after dispatch span k starts and before dispatch
             span k+1 starts.
  host       the ``serve.iteration`` spans recorded inside the window:
             how many dispatched a step (``iterations``) and their
             ``seconds`` outside ``serve.readback`` and ``serve.wait``
             (the iteration the profile's stop cut short is not recorded,
             nor is its readback counted)
  clock_offset_s  how far the device planes' clock runs behind the
             host's: the largest lead of a ``jit_step`` execution's host
             enqueue over its device start
  breakdown  ``idle_gaps`` labelled as ``trace_reduce`` labels them, with
             the ``serve.*`` spans among the candidates (the innermost
             event covering a gap's midpoint names it) and the midpoint
             moved onto the host's clock by ``clock_offset_s``

The host's enqueue of an execution is the runtime's ``DoEnqueueProgram``
event that carries the execution's ``run_id``, the stat the device's
"XLA Modules" event carries too.  Matching by ``run_id`` and not by time
is needed: on a TPU v5e the device events of one trace sat 1.23-1.27 ms
before the host events of the same execution
(``tests/data/v5e_serve_trace``), more than the host takes from a dispatch
span's start to the enqueue.  An execution with no such host event is
placed at its device start plus the offset.

Every other key and value is ``trace_reduce.reduce``'s; a trace with no
``serve.*`` event in its window reduces to exactly what it gives.
``readings`` turns the two new keys into per-iteration milliseconds.

Run a cell with this reduction in place of ``trace_reduce``'s (a
``--trace 1`` run of ``bench/run.py`` that also prints ``spans``,
``programs`` and ``readings`` to standard error):

  python3 bench/spans.py --workload <name> --seed <n> --seconds <s>
"""
from __future__ import annotations

import bisect
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce as tr  # noqa: E402

PREFIX = "serve."
DISPATCH = "serve.dispatch."
PROGRAMS = ("chunk", "verify", "decode")
STEP = "jit_step("
ENQUEUE = "DoEnqueueProgram"


def reduce_dir(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(tr.find_xplane(trace_dir)))


def reduce(pd) -> Dict:
    out = tr.reduce(pd)
    window = None
    python: List[Tuple[int, int, str]] = []
    spans: List[Tuple[int, int, str]] = []
    enqueued: Dict[int, int] = {}     # run_id -> earliest host enqueue
    devices = []
    for plane in pd.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name, s = ev.name, int(ev.start_ns)
                e = int(ev.start_ns + ev.duration_ns)
                if name == tr.WINDOW:
                    window = (s, e)
                elif name.startswith(PREFIX):
                    spans.append((s, e, name))
                elif name == ENQUEUE:
                    run = dict(ev.stats).get("run_id")
                    if run is not None:
                        enqueued[run] = min(s, enqueued.get(run, s))
                elif line.name == "python":
                    python.append((s, e, name))
    w0, w1 = window
    table: Dict[str, Dict[str, float]] = {}
    for s, e, name in spans:
        c = tr._clip(s, e, w0, w1)
        if c:
            t = table.setdefault(name, {"count": 0, "seconds": 0.0})
            t["count"] += 1
            t["seconds"] += (c[1] - c[0]) * 1e-9
    if not table:
        return out
    steps = _step_executions(devices, w0, w1)
    lead = [enqueued[run] - s for s, _, run in steps if run in enqueued]
    offset = max(0, max(lead, default=0))
    dispatch = sorted((s, name[len(DISPATCH):]) for s, _, name in spans
                      if name.startswith(DISPATCH))
    starts = [s for s, _ in dispatch]
    progs = {p: {"count": 0, "seconds": 0.0} for p in PROGRAMS}
    for s, e, run in steps:
        i = bisect.bisect_right(starts, enqueued.get(run, s + offset)) - 1
        if i < 0:
            continue
        p = progs.setdefault(dispatch[i][1], {"count": 0, "seconds": 0.0})
        p["count"] += 1
        p["seconds"] += (min(e, w1) - s) * 1e-9
    out["spans"] = table
    out["programs"] = progs
    out["host"] = _host_time(spans, w0, w1)
    out["clock_offset_s"] = offset * 1e-9
    out["breakdown"]["idle_gaps"] = [
        [tr._label(python + spans, (s + e) // 2 + offset), (e - s) * 1e-9]
        for s, e in _longest_gaps(devices, w0, w1)]
    return out


def _host_time(spans, w0: int, w1: int) -> Dict:
    iters = [(s, e) for s, e, name in spans
             if name == "serve.iteration" and w0 <= s and e <= w1]
    inner = [(s, e, name) for s, e, name in spans
             if name in ("serve.readback", "serve.wait")
             or name.startswith(DISPATCH)]
    dispatched, host = 0, 0
    for s0, e0 in iters:
        kids = [(s, e, name) for s, e, name in inner if s0 <= s and e <= e0]
        dispatched += any(name.startswith(DISPATCH) for *_, name in kids)
        host += (e0 - s0) - sum(e - s for s, e, name in kids
                                if not name.startswith(DISPATCH))
    return {"iterations": dispatched, "seconds": host * 1e-9}


def _step_executions(devices, w0: int, w1: int) -> List[Tuple[int, int, int]]:
    """(start, end, run_id) of every ``jit_step`` execution that started
    in the window, on every device."""
    out = []
    for plane in devices:
        for line in plane.lines:
            if line.name != tr.MODULES_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                if ev.name.startswith(STEP) and w0 <= s < w1:
                    out.append((s, int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats).get("run_id")))
    return out


def _longest_gaps(devices, w0: int, w1: int) -> List[Tuple[int, int]]:
    """The ten longest idle intervals of the first device, in
    ``trace_reduce.reduce``'s order."""
    first = sorted(devices, key=lambda p: p.name)[0]
    lines = {ln.name: ln for ln in first.lines}
    iv = []
    if tr.OPS_LINE in lines:
        for _, s, e in tr._events(lines[tr.OPS_LINE]):
            c = tr._clip(s, e, w0, w1)
            if c:
                iv.append(c)
    gaps = []
    prev = w0
    for s, e in tr._union(iv) + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return sorted(gaps, key=lambda g: g[0] - g[1])[:10]


def readings(trace: Dict) -> Dict[str, Optional[float]]:
    """Milliseconds per iteration from ``spans`` and ``programs``: device
    time per execution of each engine program (``<program>_ms_per_iter``),
    and the host's time per dispatched iteration outside the readback and
    the open-loop wait (``host_ms_per_iter``).  None where the trace has
    nothing to read."""
    def ms_per(m, key):
        return 1e3 * m["seconds"] / m[key] if m and m[key] else None

    progs = trace.get("programs") or {}
    out = {f"{p}_ms_per_iter": ms_per(progs.get(p), "count")
           for p in PROGRAMS}
    out["host_ms_per_iter"] = ms_per(trace.get("host"), "iterations")
    return out


def main(argv=None) -> int:
    from bench import run as harness

    def reduce_and_print(trace_dir):
        t = reduce_dir(trace_dir)
        print(json.dumps({"spans": t.get("spans"),
                          "programs": t.get("programs"),
                          "host": t.get("host"),
                          "readings": readings(t)}),
              file=sys.stderr, flush=True)
        return t

    tr.reduce_dir = reduce_and_print
    args = list(sys.argv[1:] if argv is None else argv)
    return harness.main(args + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
