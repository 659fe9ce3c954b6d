"""The redesigned serve API surface, pinned.

Two contracts ride the traffic-layer PR and must never drift:

  1. Config regroup compat — ``ServeConfig`` split into ``CacheConfig``
     / ``SpecConfig`` / ``PolicyConfig`` sub-configs, but every
     pre-regroup FLAT spelling (``ServeConfig(max_len=..., paged=...,
     spec_decode=...)``) still constructs (one DeprecationWarning),
     compares equal to the grouped spelling, and drives the engine to
     byte-identical outputs and reports.
  2. Typed report — ``serve()`` returns an ``EngineReport`` whose field
     set is stable (pinned here), whose ``as_dict()`` always carries the
     FULL schema with None for inactive subsystems, and whose mapping
     face keeps old ``report["key"]`` / ``"key" in report`` call sites
     working (a None field behaves as absent).
"""
import functools
import json

import jax
import numpy as np
import pytest

from repro.configs import base
from repro.models.lm import build_model
from repro.serve.engine import (CacheConfig, PolicyConfig, Request,
                                ServeConfig, ServeEngine, SpecConfig)
from repro.serve.kvcache import EngineReport


@functools.lru_cache(maxsize=None)
def _build():
    cfg = base.get_smoke_config("smollm-135m")
    model = build_model(cfg)
    dparams = model.convert(model.init(jax.random.PRNGKey(0)))
    return cfg, model, dparams


FLAT = dict(max_len=96, num_slots=2, paged=True, page_size=32,
            max_blocks=3, num_pages=4, prefill_chunk=32,
            spec_decode=2, spec_draft_layers=1)

GROUPED = dict(num_slots=2,
               cache=CacheConfig(max_len=96, paged=True, page_size=32,
                                 max_blocks=3, num_pages=4),
               policy=PolicyConfig(prefill_chunk=32),
               spec=SpecConfig(k=2, draft_layers=1))


# ---------------------------------------------------------------------------
# config shim
# ---------------------------------------------------------------------------

def test_flat_kwargs_warn_once_and_equal_grouped():
    with pytest.warns(DeprecationWarning, match="flat ServeConfig"):
        old = ServeConfig(**FLAT)
    new = ServeConfig(**GROUPED)
    assert old == new
    # grouped spelling is warning-free
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ServeConfig(**GROUPED)


def test_flat_readthrough_properties():
    cfg = ServeConfig(**GROUPED)
    assert cfg.max_len == 96 and cfg.paged and cfg.page_size == 32
    assert cfg.max_blocks == 3 and cfg.num_pages == 4
    assert cfg.prefill_chunk == 32 and cfg.prefix_share
    assert cfg.spec_decode == 2 and cfg.spec_draft_layers == 1


def test_unknown_kwarg_is_a_typeerror_not_a_warning():
    with pytest.raises(TypeError, match="max_lne"):
        ServeConfig(max_lne=96)


def test_flat_kwargs_keep_validation_messages():
    # the regroup must not reword the errors call sites match on
    with pytest.raises(ValueError, match=r"multiple of the packing "
                       r"word \(32\), got 48"), pytest.warns(
                           DeprecationWarning):
        ServeConfig(prefill_chunk=48)
    with pytest.raises(ValueError, match="at least one token"), \
            pytest.warns(DeprecationWarning):
        ServeConfig(spec_decode=0)


def test_both_spellings_drive_identical_engine_behavior():
    """The satellite pin: construct the SAME engine twice — once per
    spelling — and serve the same trace; outputs and every report field
    must match exactly."""
    cfg, model, dparams = _build()
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, 32, np.int64)
    reqs = lambda: [Request(rid=i, tokens=np.concatenate(
        [shared, rng2.integers(0, cfg.vocab_size, 5 + i, np.int64)])
        .astype(np.int32), max_new_tokens=6)
        for i, rng2 in enumerate([np.random.default_rng(i)
                                  for i in range(3)])]
    with pytest.warns(DeprecationWarning):
        old_cfg = ServeConfig(**FLAT)
    out_old, rep_old = ServeEngine(model, dparams, old_cfg).serve(reqs())
    out_new, rep_new = ServeEngine(
        model, dparams, ServeConfig(**GROUPED)).serve(reqs())
    assert sorted(out_old) == sorted(out_new)
    for rid in out_old:
        np.testing.assert_array_equal(out_old[rid], out_new[rid])
    d_old, d_new = rep_old.as_dict(), rep_new.as_dict()
    for key in EngineReport.field_names():
        if key in ("elapsed_s", "goodput_under_slo", "ttft_p50_s",
                   "ttft_p99_s", "tenants", "queue_wait_s"):
            continue                      # wall-clock-derived fields
        assert d_old[key] == d_new[key], key


# ---------------------------------------------------------------------------
# typed report
# ---------------------------------------------------------------------------

# THE schema pin: adding a field is an API change — extend this tuple in
# the same PR (and mirror it in docs/serving.md); removing or renaming
# one breaks stable consumers and should fail loudly here.
EXPECTED_FIELDS = (
    "total_bytes", "bytes_per_token", "bf16_equivalent_bytes",
    "compression_vs_bf16",
    "slots_total", "slots_active", "occupancy", "mean_slot_len",
    "max_slot_len", "decode_steps", "slot_utilization",
    "pages_total", "pages_used", "pages_free", "page_utilization",
    "peak_page_utilization", "page_fragmentation", "pages_reserved",
    "pages_shared", "prefix_lookups", "prefix_hits", "prefix_hit_rate",
    "cow_copies", "pages_freed_retire", "pages_freed_rollback",
    "peak_page_bytes",
    "spec_drafted", "spec_accepted", "spec_accept_rate",
    "spec_tokens_per_step", "spec_steps",
    "iterations", "dispatches_per_iteration", "unified_compiles",
    "engine_compiles", "prefill_batches", "prefill_chunks", "requests",
    "preemptions",
    "chunk_positions", "chunk_valid_positions", "prefill_positions",
    "prefill_positions_adopted", "queue_wait_s",
    "elapsed_s", "goodput_under_slo", "slo_attainment", "ttft_p50_s",
    "ttft_p99_s", "tenants",
)


def test_engine_report_field_set_is_pinned():
    assert set(EngineReport.field_names()) == set(EXPECTED_FIELDS)


def test_as_dict_always_emits_full_schema():
    rep = EngineReport(total_bytes=8, bytes_per_token=1.0,
                       bf16_equivalent_bytes=128,
                       compression_vs_bf16=16.0)
    d = rep.as_dict()
    assert set(d) == set(EXPECTED_FIELDS)
    assert d["spec_accept_rate"] is None          # inactive -> null
    json.dumps(d)


def test_mapping_face_hides_none_fields():
    rep = EngineReport(total_bytes=8, bytes_per_token=1.0,
                       bf16_equivalent_bytes=128,
                       compression_vs_bf16=16.0)
    # the pre-typed dict idioms, including the "spec off" sentinel used
    # by tests and the benchmark: a None field behaves as ABSENT
    assert "total_bytes" in rep and rep["total_bytes"] == 8
    assert "spec_accept_rate" not in rep
    with pytest.raises(KeyError):
        rep["spec_accept_rate"]
    assert rep.get("spec_accept_rate") is None
    assert rep.get("spec_accept_rate", 0.0) == 0.0
    rep["spec_accept_rate"] = 0.5
    assert "spec_accept_rate" in rep and rep["spec_accept_rate"] == 0.5
    with pytest.raises(KeyError):
        rep["not_a_field"] = 1.0
    assert "not_a_field" not in rep
    assert set(rep.keys()) <= set(EXPECTED_FIELDS)
    assert all(v is not None for _, v in rep.items())
    assert set(iter(rep)) == set(rep.keys())


def test_serve_returns_typed_report():
    cfg, model, dparams = _build()
    rng = np.random.default_rng(0)
    reqs = [Request(rid=0, tokens=rng.integers(
        0, cfg.vocab_size, 8, np.int64).astype(np.int32),
        max_new_tokens=3)]
    _, report = ServeEngine(model, dparams, ServeConfig(
        num_slots=1, cache=CacheConfig(max_len=32))).serve(reqs)
    assert isinstance(report, EngineReport)
    assert report["requests"] == 1.0
    assert report["preemptions"] == 0.0           # always set, even 0
    assert report.as_dict()["pages_total"] is None        # not paged
    assert "pages_total" not in report
