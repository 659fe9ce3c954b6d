"""Tests of the span reduction (``bench/spans.py``) on the CPU, on traces
recorded on a TPU v5e:

  JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m pytest -q bench/tests

``data/v5e_serve_trace`` is a tiny-model run of the chat cell's driver
with the Python tracer off, the whole ``serve()`` call in the profile
(``record_serve_trace.py`` recorded and trimmed it and wrote its
``expected.json``); ``data/v5e_trace`` holds no ``serve.*`` span.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import spans, trace_reduce as tr  # noqa: E402

SERVE = os.path.join(HERE, "data", "v5e_serve_trace")
PLAIN = os.path.join(HERE, "data", "v5e_trace")


def _profile(trace_dir):
    from jax.profiler import ProfileData

    return ProfileData.from_file(tr.find_xplane(trace_dir))


@pytest.fixture(scope="module")
def serve():
    with open(os.path.join(SERVE, "expected.json")) as f:
        want = json.load(f)
    pd = _profile(SERVE)
    return pd, want, spans.reduce(pd)


def _host(pd):
    return [(ln.name, ev) for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events]


def test_no_serve_span_reduces_as_trace_reduce():
    pd = _profile(PLAIN)
    assert spans.reduce(pd) == tr.reduce(pd)


def test_spans_are_on_the_thread_line(serve):
    """With the Python tracer off the spans sit on the main thread's own
    line, which ``trace_reduce`` does not read: its gap labels hold no
    span name."""
    pd, want, got = serve
    lines = {n for n, ev in _host(pd) if ev.name.startswith("serve.")}
    assert lines == set(want["span_lines"])
    assert "python" not in lines
    assert not any(n.startswith("serve.")
                   for n, _ in tr.reduce(pd)["breakdown"]["idle_gaps"])
    assert got["spans"]["serve.setup"]["count"] == 1
    dispatch = {k.rsplit(".", 1)[1]: v["count"]
                for k, v in got["spans"].items()
                if k.startswith("serve.dispatch.")}
    assert dispatch == want["dispatch_spans"]


def test_everything_else_is_trace_reduce(serve):
    pd, _, got = serve
    base = tr.reduce(pd)
    assert {k: v for k, v in got.items()
            if k not in ("spans", "programs", "host", "clock_offset_s",
                         "breakdown")} == \
        {k: v for k, v in base.items() if k != "breakdown"}
    assert got["breakdown"]["device_ops"] == base["breakdown"]["device_ops"]
    assert [s for _, s in got["breakdown"]["idle_gaps"]] == \
        [s for _, s in base["breakdown"]["idle_gaps"]]


def test_trim_keeps_every_event(tmp_path):
    """What ``trim`` leaves reads back event for event: a second trim
    changes nothing that ``ProfileData`` shows."""
    import shutil

    from bench.tests import record_serve_trace

    shutil.copytree(SERVE, tmp_path / "t")
    record_serve_trace.trim(tr.find_xplane(str(tmp_path / "t")))

    def events(d):
        return [(p.name, ln.name, ev.name, ev.start_ns, ev.duration_ns,
                 sorted((k, str(v)) for k, v in ev.stats))
                for p in _profile(d).planes for ln in p.lines
                for ev in ln.events]

    assert events(str(tmp_path / "t")) == events(SERVE)


def test_gaps_are_named_by_spans(serve):
    """A gap whose midpoint, on the host's clock, lies inside a recorded
    ``serve.iteration`` is named by a ``serve.*`` span.  In this fixture
    ``serve()`` returned before the profile stopped, so the one gap after
    its last iteration (the report and the caches' release) has no span
    and reads "host"."""
    pd, _, got = serve
    host = _host(pd)
    w0, w1 = [(ev.start_ns, ev.start_ns + ev.duration_ns)
              for _, ev in host if ev.name == tr.WINDOW][0]
    iters = [(ev.start_ns, ev.start_ns + ev.duration_ns)
             for _, ev in host if ev.name == "serve.iteration"]
    devices = [p for p in pd.planes if tr.DEVICE_PLANE.match(p.name)]
    offset = round(got["clock_offset_s"] * 1e9)
    gaps = spans._longest_gaps(devices, w0, w1)
    labels = got["breakdown"]["idle_gaps"]
    assert [d for _, d in labels] == [(e - s) * 1e-9 for s, e in gaps]
    inside = [any(s0 <= (s + e) // 2 + offset <= e0 for s0, e0 in iters)
              for s, e in gaps]
    assert sum(inside) >= len(gaps) - 1
    last = max(e0 for _, e0 in iters)
    for (name, _), (s, e), within in zip(labels, gaps, inside):
        if within:
            assert name.startswith("serve."), name
        else:
            assert (s + e) // 2 + offset > last and name == "host"


def test_every_execution_belongs_to_its_dispatch(serve):
    """One ``jit_step`` execution per dispatch span, in order: the k-th
    execution's host enqueue (matched by ``run_id``) lies between the
    k-th dispatch span's start and the next one's; the programs' counts
    and seconds add up to the executions'."""
    pd, want, got = serve
    host = _host(pd)
    w0, w1 = [(ev.start_ns, ev.start_ns + ev.duration_ns)
              for _, ev in host if ev.name == tr.WINDOW][0]
    dispatch = sorted((ev.start_ns, ev.name.rsplit(".", 1)[1])
                      for _, ev in host
                      if ev.name.startswith("serve.dispatch."))
    enqueue = {}
    for _, ev in host:
        if ev.name == spans.ENQUEUE:
            run = dict(ev.stats)["run_id"]
            enqueue[run] = min(ev.start_ns, enqueue.get(run, ev.start_ns))
    execs = sorted((ev.start_ns, ev.duration_ns, dict(ev.stats)["run_id"],
                    ev.name)
                   for p in pd.planes if p.name.startswith("/device:TPU")
                   for ln in p.lines if ln.name == tr.MODULES_LINE
                   for ev in ln.events if ev.name.startswith(spans.STEP)
                   and w0 <= ev.start_ns < w1)
    assert len(execs) == want["jit_step_executions"] == len(dispatch)
    bounds = [s for s, _ in dispatch] + [float("inf")]
    module_of = {}
    for k, (_, _, run, module) in enumerate(execs):
        assert bounds[k] <= enqueue[run] < bounds[k + 1]
        # one program per jitted step function, and the reverse
        assert module_of.setdefault(dispatch[k][1], module) == module
    assert len(set(module_of.values())) == len(module_of)

    progs = got["programs"]
    assert progs["chunk"]["count"] + progs["verify"]["count"] == len(execs)
    for prog, module in module_of.items():
        assert progs[prog]["count"] == got["modules"][module]["count"]
        assert progs[prog]["seconds"] == pytest.approx(
            got["modules"][module]["seconds"], rel=1e-12)
    # the device planes' clock runs behind the host's: every execution's
    # enqueue leads its device start by at most the offset
    lead = [enqueue[run] - s for s, _, run, _ in execs]
    assert got["clock_offset_s"] == pytest.approx(max(lead) * 1e-9)
    assert 0 < got["clock_offset_s"] < 0.01


def test_readings(serve):
    _, _, got = serve
    r = spans.readings(got)
    for p in ("chunk", "verify"):
        m = got["programs"][p]
        assert r[f"{p}_ms_per_iter"] == pytest.approx(
            1e3 * m["seconds"] / m["count"])
    assert r["decode_ms_per_iter"] is None
    h = got["host"]
    assert r["host_ms_per_iter"] == pytest.approx(
        1e3 * h["seconds"] / h["iterations"])
    # only the iteration cut short by the profile's stop goes unrecorded
    dispatched = sum(v["count"] for k, v in got["spans"].items()
                     if k.startswith("serve.dispatch."))
    assert dispatched - 1 <= h["iterations"] <= dispatched
    assert 0 < h["seconds"] < got["spans"]["serve.iteration"]["seconds"]
    assert spans.readings(tr.reduce(_profile(PLAIN))) == dict.fromkeys(
        ("chunk_ms_per_iter", "verify_ms_per_iter", "decode_ms_per_iter",
         "host_ms_per_iter"))
