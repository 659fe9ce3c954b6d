"""Reduce a JAX profiler trace (``*.xplane.pb``) of one window to the
numbers the per-layer metrics read.

The window is the host event named ``WINDOW`` (``bench/drivers/serve.py``
opens it just before it calls the system under test and closes it when
the traced seconds are up).  Within it:

  busy_s     the union of the intervals in which an operation ran on a
             device (the "XLA Ops" line of each TPU plane), averaged
             over the devices
  window_s   the window's length
  ops        device seconds per operation, summed over devices; an
             operation is named ``<program>:<instruction>`` from the
             program execution ("XLA Modules") that holds it and its
             HLO instruction name
  modules    per program (name with its fingerprint): executions that
             started in the window and their device seconds
  gaps       the seconds of each idle interval of the first device
  breakdown  the ten operations that took most time and the ten longest
             idle gaps, as ``[[name, seconds], ...]``; a gap is named by
             the innermost event of the host's Python thread that covers
             its midpoint ("host" where none does)

Times are clipped to the window.  Only ``jax.profiler.ProfileData`` is
used to read the file.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int]


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, w0: int, w1: int):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def _instruction(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return op.split(" = ", 1)[0].lstrip("%")


def _program(module: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return module.split("(", 1)[0]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(trace_dir)))


def reduce(pd) -> Dict:
    """``pd`` is a ``jax.profiler.ProfileData`` (or any object with the
    same planes / lines / events shape)."""
    host_events: List[Tuple[int, int, str]] = []
    window = None
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == WINDOW:
                    window = (s, e)
                elif line.name == "python":
                    host_events.append((s, e, name))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} host event")
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    w0, w1 = window
    busy_total = 0
    ops: Dict[str, float] = {}
    modules: Dict[str, Dict[str, float]] = {}
    first_busy: List[Interval] = []
    for k, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted(_events(lines[MODULES_LINE]), key=lambda m: m[1]) \
            if MODULES_LINE in lines else []
        for name, s, e in mods:
            if not w0 <= s < w1:
                continue
            m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += (min(e, w1) - s) * 1e-9
        starts = [s for _, s, _ in mods]
        iv = []
        for name, s, e in _events(lines[OPS_LINE]) if OPS_LINE in lines \
                else ():
            c = _clip(s, e, w0, w1)
            if not c:
                continue
            iv.append(c)
            i = bisect.bisect_right(starts, s) - 1
            prog = _program(mods[i][0]) if i >= 0 and s < mods[i][2] \
                else "-"
            key = f"{prog}:{_instruction(name)}"
            ops[key] = ops.get(key, 0.0) + (c[1] - c[0]) * 1e-9
        merged = _union(iv)
        busy_total += sum(e - s for s, e in merged)
        if k == 0:
            first_busy = merged
    busy_s = busy_total * 1e-9 / len(devices)
    gaps = []
    prev = w0
    for s, e in first_busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    top_gaps = [[_label(host_events, (s + e) // 2), (e - s) * 1e-9]
                for s, e in longest]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy_s, "window_s": (w1 - w0) * 1e-9,
            "devices": len(devices), "ops": ops, "modules": modules,
            "gaps": [(e - s) * 1e-9 for s, e in gaps],
            "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                          "idle_gaps": top_gaps}}


def _label(host_events, t: int) -> str:
    """Innermost (shortest) host event covering ``t``, else "host"."""
    best = None
    for s, e, name in host_events:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "host"
