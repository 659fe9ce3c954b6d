"""Deploy-face invariants per arch family (DESIGN.md §7.6/7.7):
QAT forward == packed deploy forward; decode step t == prefill position t."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models.lm import build_model

# representative arch per family (full matrix runs in the inline CI sweep;
# these keep the pytest wall-time sane)
ARCHS = ["smollm-135m", "mixtral-8x22b", "gemma3-27b", "hymba-1.5b",
         "xlstm-350m", "qwen1.5-32b", "bert-base-cobra"]


def _setup(arch, b=2, s=20, seed=0):
    cfg = base.get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    dparams = model.convert(params)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    fe = None
    if cfg.frontend_tokens:
        fe = jnp.asarray(rng.standard_normal(
            (b, cfg.frontend_tokens, model.frontend_dim), dtype=np.float32))
    return cfg, model, params, dparams, tokens, fe


@pytest.mark.parametrize("arch", ARCHS)
def test_qat_equals_deploy(arch):
    cfg, model, params, dparams, tokens, fe = _setup(arch)
    kw = {} if fe is None else {"frontend_embeds": fe}
    lq = model.qat_logits(params, tokens, **kw)
    ld = model.prefill_logits(dparams, tokens, **kw)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld), atol=2e-3)


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if a != "bert-base-cobra"])
def test_decode_equals_prefill(arch):
    cfg, model, params, dparams, tokens, fe = _setup(arch)
    b, s = tokens.shape
    kw = {} if fe is None else {"frontend_embeds": fe}
    max_len = s + 4 + cfg.frontend_tokens
    full = model.prefill_logits(dparams, tokens, **kw)
    _, caches = model.prefill_with_cache(dparams, tokens[:, :s - 1],
                                         max_len=max_len, **kw)
    step, caches = model.decode_step(dparams, tokens[:, s - 1:s], caches)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-3)


def test_decode_multi_step_chain():
    """Three consecutive decode steps match the teacher-forced prefill."""
    cfg, model, params, dparams, tokens, fe = _setup("smollm-135m", s=16)
    b, s = tokens.shape
    full = model.prefill_logits(dparams, tokens)
    _, caches = model.prefill_with_cache(dparams, tokens[:, :s - 3],
                                         max_len=s + 4)
    for i in range(3):
        pos = s - 3 + i
        step, caches = model.decode_step(dparams, tokens[:, pos:pos + 1],
                                         caches)
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(full[:, pos]), atol=2e-3,
                                   err_msg=f"step {i}")


def test_swa_ring_evicts_correctly():
    """mixtral smoke has window 16: a decode past the window must match a
    windowed prefill, proving ring eviction == mask semantics."""
    cfg, model, params, dparams, tokens, fe = _setup("mixtral-8x22b", s=24)
    b, s = tokens.shape
    full = model.prefill_logits(dparams, tokens)
    _, caches = model.prefill_with_cache(dparams, tokens[:, :s - 1],
                                         max_len=cfg.window_size)
    step, _ = model.decode_step(dparams, tokens[:, s - 1:s], caches)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-3)


def test_encdec_decode_matches_prefill():
    cfg = base.get_smoke_config("seamless-m4t-large-v2")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dparams = model.convert(params)
    rng = np.random.default_rng(0)
    b, s = 2, 12
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    fe = jnp.asarray(rng.standard_normal(
        (b, cfg.frontend_tokens, model.frontend_dim), dtype=np.float32))
    full = model.prefill_logits(dparams, tokens, frontend_embeds=fe)
    _, caches = model.prefill_with_cache(dparams, tokens[:, :s - 1],
                                         max_len=s + 4, frontend_embeds=fe)
    step, _ = model.decode_step(dparams, tokens[:, s - 1:s], caches)
    np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(full[:, -1]),
                               atol=2e-3)


def test_deploy_weights_are_packed():
    """Deploy weight bytes ~ 1/32 of latent fp32 (the paper's memory win)."""
    cfg, model, params, dparams, *_ = _setup("smollm-135m")

    def matmul_bytes(tree, key):
        return sum(np.prod(x.shape) * x.dtype.itemsize
                   for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
                   if key in jax.tree_util.keystr(path))

    latent = matmul_bytes(params, "w_latent")
    packed = matmul_bytes(dparams, "w_packed")
    assert packed * 100 < latent * 4  # >= 25x smaller


# ---------------------------------------------------------------------------
# deploy score-path impls (PR 6: binary-native popcount scoring)
# ---------------------------------------------------------------------------


def _mini_attn(**kw):
    from repro.models.attention import SPSAttention
    return SPSAttention(d_model=64, num_heads=4, num_kv_heads=2, **kw)


@pytest.mark.parametrize("dh", [32, 48])
def test_score_impl_paths_identical(dh):
    """popcount == mxu == dense deploy scores, prefill AND decode — the
    popcount path (the "auto" default) is exact, never approximate, so
    switching score_impl can never move accuracy numbers.  dh=48 keeps
    the Eq. 7 pad correction live."""
    from repro.models.attention import SPSAttention  # noqa: F401
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 12, 64)), np.float32)
    step = jnp.asarray(rng.normal(size=(2, 1, 64)), np.float32)
    outs, decs = {}, {}
    for si in ("popcount", "mxu", "dense", "auto"):
        attn = _mini_attn(head_dim=dh, score_impl=si)
        params = attn.convert(attn.init(jax.random.PRNGKey(0)))
        outs[si], cache = attn.deploy_prefill(params, x, cache_size=16)
        decs[si], _ = attn.deploy_decode(params, step, cache)
    for si in ("mxu", "dense", "auto"):
        np.testing.assert_array_equal(np.asarray(outs["popcount"]),
                                      np.asarray(outs[si]))
        np.testing.assert_array_equal(np.asarray(decs["popcount"]),
                                      np.asarray(decs[si]))


def test_score_impl_invalid_raises():
    attn = _mini_attn(head_dim=32, score_impl="fpga")
    params = attn.convert(attn.init(jax.random.PRNGKey(0)))
    x = jnp.zeros((1, 4, 64), jnp.float32)
    with pytest.raises(ValueError, match="score_impl"):
        attn.deploy_prefill(params, x)


@pytest.mark.parametrize("dh", [32, 48])
def test_grouped_decode_pad_correction(dh):
    """grouped_decode == ungrouped decode bitwise.  dh=48 pins the fixed
    bug: the grouped score path used ``2*pc - d_h`` without the
    ``+ 2*pad`` Eq. 7 term, silently shifting every score whenever
    d_h % 32 != 0."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 10, 64)), np.float32)
    step = jnp.asarray(rng.normal(size=(2, 1, 64)), np.float32)
    a_g = _mini_attn(head_dim=dh, grouped_decode=True)
    a_u = _mini_attn(head_dim=dh, grouped_decode=False)
    params = a_g.convert(a_g.init(jax.random.PRNGKey(0)))
    _, cache = a_u.deploy_prefill(params, x, cache_size=16)
    og, _ = a_g.deploy_decode(params, step, cache)
    ou, _ = a_u.deploy_decode(params, step, cache)
    np.testing.assert_array_equal(np.asarray(og), np.asarray(ou))



def _chunk_attend_case(groups, dh, c_len, w, seed):
    """An attention with 2 KV heads of ``groups`` query heads each, its
    deploy params with integer thresholds in [-4, 4], and random packed
    operands of ``_chunk_attend`` over a contiguous ring of W slots:
    slot 0's ring is empty, slot 1's partly filled, slot 2's wrapped;
    slots 1 and 2 bring fewer than C real queries (slot 2 maybe none)."""
    from repro.core import packing
    from repro.models.attention import SPSAttention
    rng = np.random.default_rng(seed)
    attn = SPSAttention(d_model=64, num_heads=2 * groups, num_kv_heads=2,
                        head_dim=dh)
    h, hkv, b = attn.num_heads, attn.num_kv_heads, 3
    params = attn.convert(attn.init(jax.random.PRNGKey(seed)))
    scale = (params["q_alpha"] * jnp.repeat(params["k_alpha"], groups)
             / np.sqrt(dh))
    params["sps_lambda"] = (scale * rng.integers(-4, 5, h)
                            - 1e-3 * scale)      # ceil -> the integer

    def bits(*shape):
        return packing.pack_bits(jnp.asarray(rng.integers(0, 2, shape),
                                             jnp.uint32))

    start = jnp.asarray([0, rng.integers(1, w), w + rng.integers(1, w)],
                        jnp.int32)
    operands = dict(
        q_bits=bits(b, h, c_len, dh), k_bits=bits(b, hkv, c_len, dh),
        s_v=jnp.asarray(rng.choice([-1.0, 1.0], (b, hkv, c_len, dh)),
                        jnp.float32),
        kc_old=bits(b, hkv, w, dh), vc_old=bits(b, hkv, dh, w),
        start=start,
        valid=jnp.asarray([c_len, rng.integers(1, c_len),
                           rng.integers(0, c_len)], jnp.int32),
        positions=start[:, None] + jnp.arange(c_len)[None, :])
    return attn, params, operands


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("dh", [64, 48])
@pytest.mark.parametrize("c_len", [5, 128])
def test_chunk_attend_mxu_equals_popcount(c_len, dh, groups, window):
    """``_chunk_attend``'s integer context is bit-identical whether both
    SPS products run as packed-word popcount or as int8 matmuls on the
    MXU (query heads grouped by KV head), over empty, partial and wrapped
    rings and pad rows.  dh=48 keeps the Eq. 7 pad bits live."""
    w = 96
    attn, params, operands = _chunk_attend_case(groups, dh, c_len, w,
                                                seed=c_len + dh + groups)
    ctx = {}
    for si in ("popcount", "mxu"):
        a = dataclasses.replace(attn, score_impl=si)
        ctx[si] = jax.jit(lambda p, o, a=a: a._chunk_attend(
            p, **o, ring=w, window=window))(params, operands)
    assert ctx["mxu"].dtype == jnp.int32
    c = np.asarray(ctx["popcount"])
    assert (c != 0).any() and (c != c.flat[0]).any()
    np.testing.assert_array_equal(c, np.asarray(ctx["mxu"]))


def test_score_impl_auto_resolves_by_chunk_width():
    """'auto' runs a 128-query chunk's SPS score and context on the MXU
    and keeps a 5-query verify chunk on popcount: the named scopes in
    the lowered programs' op metadata say which path each one took."""
    attn = _mini_attn(head_dim=32)
    params = attn.convert(attn.init(jax.random.PRNGKey(0)))
    cache = attn.init_cache(2, 256)

    def scopes(fn, c_len):
        x = jnp.zeros((2, c_len, 64), jnp.float32)
        text = jax.jit(fn).lower(params, x, cache).as_text(debug_info=True)
        return {s: s in text for s in (
            "sps_score/rbmm_mxu", "sps_context/rbmm_mxu",
            "sps_score/rbmm_popcount", "sps_context/rbmm_popcount")}

    chunk = scopes(lambda p, x, c: attn.deploy_prefill_chunk(p, x, c), 128)
    verify = scopes(lambda p, x, c: attn.deploy_verify_chunk(p, x, c), 5)
    assert chunk == {"sps_score/rbmm_mxu": True,
                     "sps_context/rbmm_mxu": True,
                     "sps_score/rbmm_popcount": False,
                     "sps_context/rbmm_popcount": False}
    assert verify == {"sps_score/rbmm_mxu": False,
                      "sps_context/rbmm_mxu": False,
                      "sps_score/rbmm_popcount": True,
                      "sps_context/rbmm_popcount": True}
