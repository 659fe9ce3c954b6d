"""Plain reference of the binarized deploy forward, in straightforward jnp.

It imports nothing of the program under test.  It draws its own weights
from the seed by the recipe the configuration names (``weights`` in the
configuration file: truncated normals of std 1/sqrt(fan_in) cut at two
standard deviations, one key per tensor split in a fixed tree), keeps
the signs and the per-output-channel mean |w| of each binarized matrix,
and runs one whole sequence with no cache, no paging, no batching and
no packing.

The math, per layer (activations in ``act_dtype``, the configuration's
compute precision; elementwise math in float32):

  h      = rmsnorm(x)
  q,k,v  = (sign(h) @ sign(W)) * mean|W|          binarized projections
  q,k    = rope(q), rope(k)
  s      = sign(q) . sign(k)                      integer scores per head
  p      = s >= 0, causal (and windowed) mask     SPS with zero threshold
  c      = p @ sign(v)                            integer context
  attn   = (sign(c) @ sign(Wo)) * mean|Wo|
  x      = x + attn
  u,g    = (sign(h2) @ sign(W1|W3)) * mean|W1|W3|  with h2 = rmsnorm(x)
  f      = ((silu(u) * g >= 1/2) @ sign(W2)) * mean|W2|
  x      = x + f

and the logits are rmsnorm(x) @ E^T with the tied embedding E (or the
untied output matrix where the configuration unties it).  Sign
means ``>= 0 -> +1`` throughout (the sign of zero is +1).  The +-1
products are exact in bfloat16 with float32 accumulation (every sum is
an integer below 2**24), so they carry no rounding of their own.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_PAD = 256          # the embedding table's rows round up to this
Q_BLOCK = 256            # query rows per attention block (bounds memory)


class Spec(NamedTuple):
    """Sizes of one decoder, as the reference reads them."""
    family: str            # "dense"
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    window: int            # 0 = full causal attention
    rope_theta: float
    rms_eps: float
    tie_embeddings: bool = True
    embed_scale: float = 1.0

    @property
    def vocab_padded(self) -> int:
        return -(-self.vocab_size // VOCAB_PAD) * VOCAB_PAD


def spec_from_config(cfg: Dict) -> Spec:
    """Build the reference's ``Spec`` from a configuration file's dict
    (the ``reference`` block of ``bench/configs/<name>.json``)."""
    r = cfg["reference"]
    return Spec(**{f: r[f] for f in Spec._fields if f in r})


# -- weights ---------------------------------------------------------------


def _normal(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def _binary(key, fan_in, fan_out):
    """(signs (in, out) as +-1 bfloat16, scale (out,) float32)."""
    w = _normal(key, (fan_in, fan_out), 1.0 / math.sqrt(fan_in))
    return (jnp.where(w >= 0, 1.0, -1.0).astype(jnp.bfloat16),
            jnp.mean(jnp.abs(w), axis=0))


@functools.partial(jax.jit, static_argnames=("spec",))
def stack_scales(keys, spec: "Spec") -> Dict:
    """Every layer's per-channel scales at once: the scales are float32
    means of |w| over 1,000s of values, whose last bit depends on the
    order of the sum, and the TPU compiler orders a sum over a stack of
    layers as it does for the whole stack's generation.  A last-bit
    difference is enough to start a cascade of flipped signs (see the
    checks), so the scales come from the same stacked shape."""
    return jax.vmap(lambda k: {n: v[1] for n, v in
                               layer_weights(k, spec).items()
                               if isinstance(v, tuple)})(keys)


def layer_keys(seed: int, spec: Spec):
    """(root keys, per-layer keys): the root key splits four ways
    (embedding, output head, -, layer stack) and the stack key splits
    one key per layer."""
    root = jax.random.split(jax.random.PRNGKey(seed), 4)
    return root, jax.random.split(root[3], spec.num_layers)


def embedding(key, spec: Spec):
    return _normal(key, (spec.vocab_padded, spec.d_model),
                   spec.d_model ** -0.5) * spec.embed_scale


def layer_weights(key, spec: Spec) -> Dict:
    """One layer's binarized weights.  The layer key splits one way per
    part, parts in name order (attn, ffn); attention splits into wq, wk,
    wv, wo; the FFN into w1, w2, w3."""
    d, hd = spec.d_model, spec.head_dim
    parts = dict(zip(["attn", "ffn"], jax.random.split(key, 2)))
    ka = jax.random.split(parts["attn"], 4)
    kf = jax.random.split(parts["ffn"], 3)
    w = {"wq": _binary(ka[0], d, spec.num_heads * hd),
         "wk": _binary(ka[1], d, spec.num_kv_heads * hd),
         "wv": _binary(ka[2], d, spec.num_kv_heads * hd),
         "wo": _binary(ka[3], spec.num_heads * hd, d),
         "w1": _binary(kf[0], d, spec.d_ff),
         "w2": _binary(kf[1], spec.d_ff, d),
         "w3": _binary(kf[2], d, spec.d_ff)}
    return w


# -- pieces ----------------------------------------------------------------


def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(jnp.bfloat16)


def _bmm(s, w):
    """+-1 (or {0,1}) activations times (signs, scale) -> float32."""
    signs, scale = w
    c = jnp.einsum("...k,kn->...n", s.astype(jnp.bfloat16), signs,
                   preferred_element_type=jnp.float32)
    return c * scale


def _rmsnorm(x, eps, dt):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt)


def _rope(x, pos, theta):
    """x (..., S, H, hd) in the compute dtype; math in float32."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[..., :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(spec: Spec, w, h, pos, dt):
    """h (B, S, d): causal (windowed) SPS attention, query rows in blocks
    of ``Q_BLOCK``."""
    b, s_len = h.shape[:2]
    hq, hk, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    sx = _sign(h)
    q = _bmm(sx, w["wq"]).astype(dt).reshape(b, s_len, hq, hd)
    k = _bmm(sx, w["wk"]).astype(dt).reshape(b, s_len, hk, hd)
    v = _bmm(sx, w["wv"]).astype(dt).reshape(b, s_len, hk, hd)
    sq = _sign(_rope(q, pos, spec.rope_theta))
    sk = jnp.repeat(_sign(_rope(k, pos, spec.rope_theta)), hq // hk, axis=2)
    sv = jnp.repeat(_sign(v), hq // hk, axis=2)
    nblk = s_len // Q_BLOCK

    def block(i):
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(sq, i * Q_BLOCK, Q_BLOCK, axis=1)
        score = jnp.einsum("bqhd,bkhd->bhqk", qb, sk,
                           preferred_element_type=jnp.float32)
        cols = jnp.arange(s_len)
        live = cols[None, :] <= rows[:, None]
        if spec.window:
            live = live & (cols[None, :] > rows[:, None] - spec.window)
        p = ((score >= 0) & live).astype(jnp.bfloat16)
        return jnp.einsum("bhqk,bkhd->bqhd", p, sv,
                          preferred_element_type=jnp.float32)

    ctx = jax.lax.map(block, jnp.arange(nblk))         # (nblk, B, Q, H, hd)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s_len, hq * hd)
    return _bmm(_sign(ctx), w["wo"]).astype(dt)


def _ffn(spec: Spec, w, h):
    s = _sign(h)
    u, g = _bmm(s, w["w1"]), _bmm(s, w["w3"])
    hb = (jax.nn.silu(u) * g >= 0.5)
    return _bmm(hb, w["w2"])


@functools.partial(jax.jit, static_argnames=("spec", "dt"))
def _layer(key, scales, x, pos, spec: Spec, dt):
    w = {n: (v[0], scales[n]) if isinstance(v, tuple) else v
         for n, v in layer_weights(key, spec).items()}
    h = _rmsnorm(x, spec.rms_eps, dt)
    x = x + _attention(spec, w, h, pos, dt)
    f = _ffn(spec, w, _rmsnorm(x, spec.rms_eps, dt)).astype(dt)
    return x + f


def head_weights(root, spec: Spec):
    """(vocab_padded, d) output matrix: the embedding when tied, else its
    own truncated normal of std 1/sqrt(d) from the second root key."""
    if spec.tie_embeddings:
        return embedding(root[0], spec)
    return _normal(root[1], (spec.d_model, spec.vocab_padded),
                   1.0 / math.sqrt(spec.d_model)).T


@functools.partial(jax.jit, static_argnames=("spec", "dt"))
def _embed(root, tokens, spec: Spec, dt):
    e = embedding(root[0], spec)
    return e[tokens].astype(dt) * jnp.sqrt(jnp.float32(spec.d_model)
                                           ).astype(dt)


@functools.partial(jax.jit, static_argnames=("spec", "dt"))
def _head(root, x, rows, spec: Spec, dt):
    e = head_weights(root, spec)
    hn = _rmsnorm(x[rows], spec.rms_eps, dt).astype(jnp.float32)
    lg = jnp.matmul(hn, e.T, precision=jax.lax.Precision.HIGHEST)
    return lg[..., :spec.vocab_size]


def logits(spec: Spec, seed: int, seqs: Sequence[Sequence[int]],
           rows: Sequence[Sequence[int]], act_dtype: str = "bfloat16"):
    """For each sequence of ``seqs``, the logits (len(rows[i]), vocab)
    float32 at its positions ``rows[i]``.  All sequences run together,
    layer by layer, each layer's weights drawn once; they are padded at
    their ends to one power of two of at least ``Q_BLOCK`` (causal:
    padding changes no earlier position), so few lengths compile."""
    dt = jnp.dtype(act_dtype)
    n = max(len(t) for t in seqs)
    s_len = Q_BLOCK
    while s_len < n:
        s_len *= 2
    toks = np.zeros((len(seqs), s_len), np.int32)
    for i, t in enumerate(seqs):
        toks[i, :len(t)] = np.asarray(t, np.int32)
    root, lkeys = layer_keys(seed, spec)
    scales = stack_scales(lkeys, spec=spec)
    x = _embed(root, jnp.asarray(toks), spec=spec, dt=dt)
    pos = jnp.arange(s_len, dtype=jnp.int32)
    for i in range(spec.num_layers):
        x = _layer(lkeys[i], jax.tree.map(lambda t: t[i], scales), x, pos,
                   spec=spec, dt=dt)
    out = []
    for i, r in enumerate(rows):
        idx = np.zeros((s_len,), np.int32)
        idx[:len(r)] = np.asarray(r, np.int32)
        lg = _head(root, x[i], jnp.asarray(idx), spec=spec, dt=dt)
        out.append(np.asarray(lg)[:len(r)])
    return out
