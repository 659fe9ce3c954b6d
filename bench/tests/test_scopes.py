"""Tests of the scope reduction (``bench/scopes.py``) on the CPU:

  JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m pytest -q bench/tests

The programs of the executions come from the v5e fixture of
``test_spans.py``; the op events are made here, in the perfetto
export's form (its ``traceEvents``), since the fixture keeps no export.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import scopes, trace_reduce as tr  # noqa: E402

SERVE = os.path.join(HERE, "data", "v5e_serve_trace")


def test_programs_by_device_match_the_dispatch_spans():
    """Every ``jit_step`` execution of the fixture gets the program of
    the dispatch that enqueued it: as many of each as there are
    ``serve.dispatch.*`` spans."""
    from jax.profiler import ProfileData

    with open(os.path.join(SERVE, "expected.json")) as f:
        want = json.load(f)
    got = scopes.programs_by_device(
        ProfileData.from_file(tr.find_xplane(SERVE)))
    progs = [p for ex in got.values() for p in ex]
    assert len(progs) == want["jit_step_executions"]
    assert {p: progs.count(p) for p in set(progs)} == want["dispatch_spans"]


def _trace(ops, modules):
    meta = [{"ph": "M", "pid": 7, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
             "args": {"name": tr.MODULES_LINE}},
            {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
             "args": {"name": tr.OPS_LINE}},
            {"ph": "M", "pid": 9, "name": "process_name",
             "args": {"name": "/host:CPU"}}]
    mods = [{"ph": "X", "pid": 7, "tid": 1, "ts": s, "dur": d,
             "name": f"jit_step({n})"} for n, (s, d) in enumerate(modules)]
    evs = [{"ph": "X", "pid": 7, "tid": 2, "ts": s, "dur": d, "name": "op",
            "args": {"tf_op": op}} for s, d, op in ops]
    host = [{"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 1000,
             "name": "jit(step)/sps_score/xor", "args": {}}]
    return meta + mods + evs + host


def test_scope_times_by_program_and_self_time():
    """Ops fall to the execution that holds them; an op nested in
    another (a loop body) leaves its parent only the rest; an op that
    names two scopes counts in both; host events count in none."""
    events = _trace(
        ops=[(0, 10, "jit(step)/paged_gather/gather"),
             (10, 30, "jit(step)/while/body/sps_score/rbmm_mxu/dot"),
             (40, 50, "jit(step)/while"),
             (45, 20, "jit(step)/while/body/sps_context/rbmm_mxu/dot"),
             (200, 8, "jit(step)/sps_score/rbmm_popcount/xor"),
             (208, 2, "jit(step)/sps_context/rbmm_popcount/and")],
        modules=[(0, 100), (200, 20)])
    got = scopes.scope_times(events, {"/device:TPU:0": ["chunk", "verify"]})
    chunk, verify = got["chunk"], got["verify"]
    assert chunk["count"] == verify["count"] == 1
    assert chunk["seconds"] == pytest.approx(90e-6)
    assert chunk["scopes"] == pytest.approx(
        {"sps_score": 30e-6, "sps_context": 20e-6, "paged_gather": 10e-6,
         "rbmm_popcount": 0.0, "rbmm_mxu": 50e-6})
    assert verify["seconds"] == pytest.approx(10e-6)
    assert verify["scopes"]["rbmm_popcount"] == pytest.approx(10e-6)
    shares = scopes.shares(got)
    assert shares["verify"]["sps_score"] == pytest.approx(80.0)
    assert shares["chunk"]["rbmm_mxu"] == pytest.approx(100 * 50 / 90)


def test_scope_times_refuse_unpaired_executions():
    events = _trace(ops=[], modules=[(0, 100)])
    with pytest.raises(ValueError, match="executions"):
        scopes.scope_times(events, {"/device:TPU:0": ["chunk", "verify"]})
