"""Spans and work counters of ``ServeEngine.serve``.

The counters (``EngineReport`` "work" group) are pinned at values counted
by hand on a tiny model; the spans are read back from a CPU profile of a
whole ``serve()`` call with ``jax.profiler.ProfileData``.
"""
import functools
import glob
import types

import jax
import numpy as np
import pytest

from repro.configs import base
from repro.models.lm import build_model
from repro.serve import engine as engine_mod
from repro.serve.engine import (CacheConfig, PolicyConfig, Request,
                                ServeConfig, ServeEngine, SpecConfig)

PAGE = 32
CHUNK = 32


@functools.lru_cache(maxsize=None)
def _engine():
    """Two slots, paged with prefix sharing, chunk 32, speculative k=2:
    every span and counter of the loop has work to record."""
    cfg = base.get_smoke_config("smollm-135m")
    model = build_model(cfg)
    dparams = model.convert(model.init(jax.random.PRNGKey(0)))
    return cfg, ServeEngine(model, dparams, ServeConfig(
        num_slots=2,
        cache=CacheConfig(max_len=96, paged=True, page_size=PAGE),
        policy=PolicyConfig(prefill_chunk=CHUNK), spec=SpecConfig(k=2)))


def _tokens(vocab, n, seed):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def test_chunk_step_positions_hand_counted():
    """A (4 tokens) and B (40 tokens) arrive together.  Step 1: A's whole
    prompt and B's first chunk, 4 + 32 valid of 2 x 32.  Step 2: A's
    decode row (1) and B's last 8 prompt tokens, 9 valid of 2 x 32.
    Then only verify steps, which the chunk counters do not see."""
    cfg, eng = _engine()
    reqs = [Request(rid=0, tokens=_tokens(cfg.vocab_size, 4, 1),
                    max_new_tokens=3),
            Request(rid=1, tokens=_tokens(cfg.vocab_size, 40, 2),
                    max_new_tokens=3)]
    _, rep = eng.serve(reqs)
    assert rep.chunk_positions == 2 * (2 * CHUNK)
    assert rep.chunk_valid_positions == (4 + 32) + (1 + 8)
    assert rep.prefill_positions == 4 + 40
    assert rep.prefill_positions_adopted == 0
    assert rep.spec_steps == rep.iterations - 2


def test_adopted_prefix_page_is_counted_as_recomputed():
    """B's prompt opens with A's first page, so B's first page is adopted
    at admission; the chunk step still computes its 32 positions."""
    cfg, eng = _engine()
    shared = _tokens(cfg.vocab_size, PAGE, 3)
    reqs = [Request(rid=0, max_new_tokens=2, tokens=np.concatenate(
                [shared, _tokens(cfg.vocab_size, 8, 4)])),
            Request(rid=1, max_new_tokens=2, tokens=np.concatenate(
                [shared, _tokens(cfg.vocab_size, 5, 5)]))]
    _, rep = eng.serve(reqs)
    assert rep.prefix_hits == 1
    assert rep.prefill_positions == 40 + 37
    assert rep.prefill_positions_adopted == PAGE
    # two chunk steps with no decode row: every valid position is prompt
    assert rep.chunk_positions == 2 * (2 * CHUNK)
    assert rep.chunk_valid_positions == rep.prefill_positions


def test_queue_wait_is_admission_minus_arrival(monkeypatch):
    """A fake engine clock that advances one second per streamed token.
    Requests 0 and 1 fill both slots at 0 s and each stream 2 tokens
    (the clock reads 4 when both retire); request 2, due at 1.5 s while
    every slot was full, is admitted at 4 s."""
    cfg, eng = _engine()
    clock = [0.0]
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: clock[0], sleep=lambda s: None))

    def tick(rid, index, token):
        clock[0] += 1.0

    reqs = [Request(rid=i, tokens=_tokens(cfg.vocab_size, 4, 10 + i),
                    max_new_tokens=(2, 2, 1)[i],
                    arrival_s=(0.0, 0.0, 1.5)[i]) for i in range(3)]
    out, rep = eng.serve(reqs, stream_cb=tick)
    assert rep.queue_wait_s == {0: 0.0, 1: 0.0, 2: 2.5}
    assert all(len(out[i]) == reqs[i].max_new_tokens for i in range(3))


def _trace_requests(vocab):
    shared = _tokens(vocab, PAGE, 20)
    return [Request(rid=i, max_new_tokens=5, tokens=np.concatenate(
        [shared, _tokens(vocab, n, 21 + i)]))
        for i, n in enumerate((12, 40, 3))]


def _serve_spans(eng, reqs, trace_dir):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        out, rep = eng.serve(reqs)
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return out, rep, spans


def test_spans_of_a_profiled_serve(tmp_path):
    cfg, eng = _engine()
    reqs = _trace_requests(cfg.vocab_size)
    plain, _ = eng.serve(reqs)
    out, rep, spans = _serve_spans(eng, _trace_requests(cfg.vocab_size),
                                   tmp_path)
    # the profiler changes no served token
    assert sorted(out) == sorted(plain)
    for rid in plain:
        np.testing.assert_array_equal(out[rid], plain[rid])

    names = [n for n, *_ in spans]
    dispatch = [n for n in names if n.startswith("serve.dispatch.")]
    assert len(dispatch) == rep.iterations
    assert dispatch.count("serve.dispatch.verify") == rep.spec_steps
    assert dispatch.count("serve.dispatch.chunk") == \
        rep.iterations - rep.spec_steps
    assert names.count("serve.readback") == rep.iterations
    assert names.count("serve.setup") == 1
    admitted = sorted(a["rid"] for n, _, _, a in spans
                      if n == "serve.admit" and "rid" in a)
    assert admitted == [r.rid for r in reqs]
    assert {"serve.emit", "serve.prepare", "serve.grow",
            "serve.sync_tables"} <= set(names)

    iters = sorted((s, e, a["step_num"]) for n, s, e, a in spans
                   if n == "serve.iteration")
    assert [k for _, _, k in iters] == list(range(len(iters)))
    for name, s, e, _ in spans:
        if name in ("serve.iteration", "serve.setup"):
            continue
        assert any(s0 <= s and e <= e0 for s0, e0, _ in iters), name


def test_work_counters_repeat_exactly():
    """Two calls on the same requests serve the same tokens and count
    the same work, so a later change can be judged on these counts."""
    cfg, eng = _engine()
    a, rep_a = eng.serve(_trace_requests(cfg.vocab_size))
    b, rep_b = eng.serve(_trace_requests(cfg.vocab_size))
    for rid in a:
        np.testing.assert_array_equal(a[rid], b[rid])
    for key in ("chunk_positions", "chunk_valid_positions",
                "prefill_positions", "prefill_positions_adopted"):
        assert rep_a[key] == rep_b[key] > 0, key
    assert sorted(rep_a.queue_wait_s) == [0, 1, 2]
    assert all(w >= 0 for w in rep_a.queue_wait_s.values())


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_work_counters_without_speculation(paged):
    """The plain decode path counts the same way.  Paged, request 1
    adopts request 0's first page at admission, and request 2 adopts it
    from request 1, which is still in flight when request 0 retires;
    contiguous caches adopt nothing."""
    cfg = base.get_smoke_config("smollm-135m")
    _, eng = _engine()
    plain = ServeEngine(eng.model, eng.dparams, ServeConfig(
        num_slots=2, cache=CacheConfig(max_len=96, paged=paged,
                                       page_size=PAGE),
        policy=PolicyConfig(prefill_chunk=CHUNK)))
    out, rep = plain.serve(_trace_requests(cfg.vocab_size))
    assert rep.prefill_positions == sum(
        len(r.tokens) for r in _trace_requests(cfg.vocab_size))
    assert rep.chunk_positions % (2 * CHUNK) == 0
    assert rep.chunk_valid_positions >= rep.prefill_positions
    assert rep.prefill_positions_adopted == (2 * PAGE if paged else 0)
    assert rep.get("prefix_hits", 0) == (2 if paged else 0)
