"""Tests of the benchmark itself, on the CPU.

  JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m pytest -q bench/tests

They check the trace reduction on a trace recorded on a TPU v5e, the
operation and byte counts against hand counts, the traffic generator's
promise (same work for every seed, in another order), the refusal to run
without a chip, that a token altered where the program produces it makes
a run not correct, and that the lower-precision control fails the
comparison at a size a test can hold.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import counts, gen, trace_reduce  # noqa: E402

FIXTURE = os.path.join(HERE, "data", "v5e_trace")


# -- trace reduction ---------------------------------------------------------


def test_trace_reduction_on_recorded_v5e_trace():
    """The fixture (bench/tests/data/v5e_trace) was recorded on one TPU
    v5e with the Python tracer on: inside a ``bench_window`` host
    annotation of 16,260,569 ns, five rounds of a 2 ms host sleep
    followed by two small jitted programs (a 512 x 512 matmul with tanh,
    and a scaled sum), each executed five times.  The ten program
    executions last 37,370 ns on the device; the operations inside them
    37,323 ns (expected.json)."""
    with open(os.path.join(FIXTURE, "expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_dir(FIXTURE)
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(16_260_569e-9, rel=1e-12)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    module_s = sum(m["seconds"] for m in got["modules"].values())
    assert module_s == pytest.approx(37_370e-9, rel=1e-12)
    assert 0.95 * module_s < got["busy_s"] <= module_s
    assert sorted(m["count"] for m in got["modules"].values()) == [5, 5]
    assert all(k.startswith("jit__lambda:") for k in got["ops"])
    assert sum(got["ops"].values()) >= got["busy_s"]
    gaps = got["breakdown"]["idle_gaps"]
    assert len(gaps) == 10 and gaps[0][1] >= 0.002
    assert gaps[0][0] == "$time sleep"
    assert sum(got["gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-9)


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == \
        [(0, 3), (5, 9)]


# -- counts ------------------------------------------------------------------


TINY = {"reference": {
    "family": "dense", "num_layers": 2, "d_model": 64, "num_heads": 2,
    "num_kv_heads": 1, "head_dim": 32, "d_ff": 128, "vocab_size": 300,
    "window": 0, "tie_embeddings": True}}


def test_counts_match_hand_counts():
    # per layer: wq 64x64 + wk, wv 64x32 each + wo 64x64 + 3 x 64x128
    assert counts.binary_params_per_layer(TINY) == \
        4096 + 2048 + 2048 + 4096 + 3 * 8192
    assert counts.dense_ops_per_position(TINY) == 2 * 2 * 36864
    # 2 layers x 4 x 2 heads x 32
    assert counts.attn_ops_per_key(TINY) == 512
    # vocab 300 pads to 512 rows: 2 x 64 x 512
    assert counts.head_ops_per_row(TINY) == 65536
    u = counts.unified_step(TINY, slots=2, width=4, ring=8)
    # 8 positions, each over 8 cached + 4 chunk keys, 2 logit rows
    assert u["ops"] == 8 * 147456 + 8 * 12 * 512 + 2 * 65536
    # f32 embedding 512 x 64, per layer 36864 bits + 4 B per out channel
    # (64 + 64 + 64 + 256 + 64), K+V bits: 2 slots x 8 keys x 2 layers x
    # 1 kv head x 2 x 32 bits
    assert u["bytes"] == 512 * 64 * 4 + 2 * (36864 // 8 + 4 * 512) + \
        2 * 8 * 2 * 1 * 2 * 32 // 8
    d = counts.decode_step(TINY, slots=3, ring=8)
    assert d["ops"] == 3 * 147456 + 3 * 8 * 512 + 3 * 65536
    s = counts.spec_step(TINY, slots=1, k=2, ring=8, draft_layers=1,
                         draft_ring=16)
    draft = 73728 + 16 * 256 + 65536 + 1 * 256    # one row, one layer
    verify = 3 * 147456 + 3 * 11 * 512 + 3 * 65536
    assert s["ops"] == 3 * draft + verify


def test_useful_ops_window_caps_keys():
    cfg = {"reference": dict(TINY["reference"], window=4)}
    assert counts.keys_seen(cfg, 0) == 1
    assert counts.keys_seen(cfg, 10) == 4
    assert counts.useful_ops(cfg, 10, True) == 147456 + 4 * 512 + 65536


# -- traffic -----------------------------------------------------------------


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _uniform_bursts():
    """The cell's mix with Pareto bursts, no shared prefix and a uniform
    output draw: the generator's other branches."""
    mix = _mix("chat-shared-spec4")
    del mix["shared_prefix"]
    mix["arrival"] = {"kind": "pareto", "shape": 1.5}
    mix["output"] = {"dist": "uniform", "min": 512, "max": 1536}
    return mix


@pytest.mark.parametrize("make", [lambda: _mix("chat-shared-spec4"),
                                  _uniform_bursts],
                         ids=["chat-shared-spec4", "uniform-bursts"])
def test_every_seed_offers_the_same_schedule(make):
    mix = make()
    a = gen.generate(mix, 2**31 + 5, 10.0, 1000)
    b = gen.generate(mix, 7, 10.0, 1000)
    again = gen.generate(mix, 7, 10.0, 1000)
    assert len(a) == len(b) == gen.population_size(mix, 10.0)
    for x, y, z in zip(a, b, again):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert len(x.prompt) == len(y.prompt) and x.shared == y.shared
        assert np.array_equal(y.prompt, z.prompt)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(0 <= r.due_s < 10.0 for r in a)
    assert max(len(r.prompt) + r.max_new_tokens for r in a) <= \
        gen.longest_request(mix)
    sp = mix.get("shared_prefix")
    if sp:
        opened = [r for r in a if r.shared >= 0]
        assert len(opened) == round(sp["share"] * len(a))
        firsts = {r.shared: r.prompt[:sp["tokens"]] for r in opened}
        assert all(np.array_equal(r.prompt[:sp["tokens"]], firsts[r.shared])
                   for r in opened)


# -- refusal -----------------------------------------------------------------


def _run_cmd(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
         ["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


@pytest.mark.parametrize("env_extra", [{}, {"REPRO_FORCE_INTERPRET": "1"}])
def test_no_result_without_a_tpu(env_extra):
    p = _run_cmd(ROOT, env_extra)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_no_result_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run_cmd(str(tmp_path), {})
    assert p.returncode != 0
    assert "correct" not in p.stdout


# -- correctness: a fault and the control fail ---------------------------------


def test_altered_token_is_not_correct():
    """Drive a whole tiny run with the program's greedy sampler replaced
    by one that serves the least likely token where it produces one: the
    run must come out not correct.  (A fault confined to one pool row is
    diluted by the other rows' tokens in the mean gap; ``PERF.md``.)"""
    import jax.numpy as jnp

    from bench.tests import tiny
    from repro.serve import sampler

    real, real_accept = sampler.greedy, sampler.speculative_accept

    def altered(logits, key=None):
        return jnp.argmin(logits, axis=-1).astype(real(logits).dtype)

    def altered_accept(drafts, q_probs, logits, key, **kw):
        # the verify step's own token, at the first rejected position
        out, n = real_accept(drafts, q_probs, logits, key, **kw)
        at = jnp.arange(out.shape[1])[None, :] == n[:, None]
        return jnp.where(at, altered(logits), out), n

    sound, _ = tiny.run_cell("granite-3-2b.chat-shared-spec4")
    assert sound["correct"]
    sampler.greedy = altered
    sampler.speculative_accept = altered_accept
    try:
        res, _ = tiny.run_cell("granite-3-2b.chat-shared-spec4")
    finally:
        sampler.greedy, sampler.speculative_accept = real, real_accept
    assert not res["correct"]
    gap = res["checks"]["mean_gap"]
    assert gap["value"] > gap["limit"]


def test_lower_precision_control_fails():
    """The control: the reference computed in float8 in the program's
    place.  On the served tokens of a sound tiny run at 8 layers of width
    512, the tokens it puts first fail the cell's limits."""
    from bench.checks import token_match
    from bench.reference import binary_lm
    from bench.tests import tiny

    res, run = tiny.run_cell("granite-3-2b.chat-shared-spec4",
                             size=dict(num_layers=8, d_model=512,
                                       num_heads=8, num_kv_heads=2,
                                       d_ff=2048, vocab_size=4096))
    assert res["correct"]
    spec = binary_lm.spec_from_config(run.ctx.cfg)
    served = run.check["served"]
    pick = token_match.select(run.check["requests"], served, 3, 200, 3)
    control = token_match.verdict(token_match.control_gaps(
        binary_lm, spec, run.ctx.args.seed, pick, served, "float8_e4m3fn"),
        run.unfinished, run.ctx.cell["check"])
    assert not control["correct"]
    assert control["failed"] == len(pick)
