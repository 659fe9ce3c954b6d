"""The per-layer metrics that read ``EngineReport``'s work counters
(``bench/metrics/padded_share.py``, ``prefix_recompute_share.py``,
``queue_wait_p95_ms.py``), on a run record made by hand:

  JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m pytest -q bench/tests
"""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as harness  # noqa: E402

COUNTERS = {"chunk_positions": 2048.0, "chunk_valid_positions": 256.0,
            "prefill_positions": 200.0, "prefill_positions_adopted": 128.0,
            "queue_wait_s": {0: 0.1, 1: 0.3, 2: 5.0}}
DUE = {0: 0.0, 1: 0.5, 2: 0.2}


def _read(name, counters, trace=None):
    reqs = [types.SimpleNamespace(rid=r, due_s=d) for r, d in DUE.items()]
    run = types.SimpleNamespace(counters=counters, trace=trace,
                                check={"requests": reqs})
    return harness.load_module("metrics", name).read(run)


def test_shares_of_the_chunk_steps():
    assert _read("padded_share", COUNTERS) == pytest.approx(87.5)
    assert _read("prefix_recompute_share", COUNTERS) == pytest.approx(64.0)


def test_queue_wait_reads_the_waits_admitted_in_the_profile():
    """Untraced every wait counts; traced (a 1-s profile) only those of
    requests admitted inside it: request 2, due at 0.2 s, was admitted
    at 5.2 s."""
    assert _read("queue_wait_p95_ms", COUNTERS) == pytest.approx(
        300.0 + 0.9 * 4700.0)
    assert _read("queue_wait_p95_ms", COUNTERS, {"window_s": 1.0}) == \
        pytest.approx(100.0 + 0.95 * 200.0)


@pytest.mark.parametrize("name", ["padded_share", "prefix_recompute_share",
                                  "queue_wait_p95_ms"])
def test_nothing_to_read_without_the_counters(name):
    """A program that does not count the work (one older than the
    counters) gives no reading and raises nothing, traced or not."""
    assert _read(name, {"iterations": 10.0}) is None
    assert _read(name, {"iterations": 10.0}, {"window_s": 1.0}) is None
