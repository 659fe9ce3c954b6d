"""SPS binary attention (paper §III-A) — QAT twin + deploy paths.

One module, three execution faces, numerically identical where they overlap:

  qat(...)            BiT-style latent training forward with SPS-STE (or the
                      BiT softmax+elastic-binarization teacher, for
                      calibration/distillation — ``attn_mode="bit_softmax"``).
  deploy_prefill(...) packed-bit forward (M1 -> M2 -> M3 -> M4), returns the
                      binary KV cache.
  deploy_decode(...)  single-token step against the packed cache — the fully
                      binary datapath: K packed along d_h, V^T packed along
                      the sequence dim, probs packed in-flight (Eq. 7 both
                      schemes), 1 bit/value end to end.

Attention is *chunked over query rows everywhere* (lax.map over q-chunks):
SPS has no softmax state, so chunks combine associatively and the l x l
score matrix never materializes.  ``repro.kernels.sps_attn`` is a fused
Pallas form of the same chunk body; no model path calls it (only its tests
do), and the TPU compiler does not accept it yet.

Supports GQA (kv heads broadcast to q heads), RoPE (applied on the fp
projections *before* per-head binarization; BERT-style archs skip it and use
the fused M1 binary-out path), sliding windows (static or per-layer traced —
gemma's 5:1 local:global stacks scan with the window as per-layer data),
cross-attention (enc-dec), and the three SPS threshold granularities.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import binarize, packing, rbmm, sps
from repro.kernels.paged_attn import ops as paged_attn_ops
from repro.models import nn
from repro.models.linear import BinaryDense

Array = jax.Array
Params = Dict[str, Any]

ROW_TABLE = 512  # row-granularity lambda table (paper's l=512); longer rows clamp

# Default q-row chunk for the chunked attention scan.
Q_CHUNK = 256

# Deploy score-path impls (``SPSAttention.score_impl``): "auto" resolves by
# the query rows a slot brings, popcount up to 16 and mxu above (chunks of
# a cache-resuming prefill) — see ``SPSAttention._score_impl``.
SCORE_IMPLS = ("auto", "popcount", "mxu", "dense")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, dh), positions: (..., S) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Binary KV cache.  k_bits: (B, Hkv, W, dh/32) packed along d_h;
    vt_bits: (B, Hkv, dh, W/32) packed along the (ring) sequence dim;
    length: (B,) int32 — per-sequence tokens written (ring wraps at W).
    Per-sequence lengths are what let a slot pool decode sequences at
    different positions in one batched step (continuous batching); legacy
    scalar lengths still broadcast fine everywhere they are read."""
    k_bits: Array
    vt_bits: Array
    length: Array


class PagedKVCache(NamedTuple):
    """Paged binary KV cache: a page arena plus per-sequence block tables.

    Instead of one contiguous W-token ring per sequence, tokens live in
    fixed-size pages drawn from a shared arena.  Logical ring arithmetic is
    unchanged — token position t occupies logical ring slot s = t % ring_len
    — but slot s resolves through the block table to a physical page:
    page ``block_table[b, s // page_size]``, offset ``s % page_size``.

    Physical page 0 is a reserved *trash page*: unallocated block-table
    entries are 0, so decode writes from free/retired pool slots (which
    still run inside the jit'd pooled step) land there instead of
    corrupting live pages.  Usable page ids are 1..num_pages.

    Fields:
      k_pages:     (P+1, Hkv, page_size, dh/32) uint32 — K bits packed
                   along d_h, one row per page token.
      vt_pages:    (P+1, Hkv, dh, page_size/32) uint32 — V^T bits packed
                   along the page's token axis (page_size % 32 == 0, so
                   packing words never straddle pages).
      block_table: (B, num_blocks) int32 physical page ids (0 = unmapped).
      length:      (B,) int32 tokens written per sequence.
      ring_len:    () int32 logical ring length (window for SWA layers,
                   num_blocks * page_size for full attention).
    """
    k_pages: Array
    vt_pages: Array
    block_table: Array
    length: Array
    ring_len: Array


def _check_page_size(page_size: int) -> None:
    """Single source of the page-size rule: a positive multiple of the
    32-bit packing word, so V^T bit-packing never straddles pages."""
    if page_size <= 0 or page_size % packing.WORD:
        raise ValueError(
            f"page_size must be a positive multiple of the packing "
            f"word ({packing.WORD}), got {page_size}")


@dataclasses.dataclass(frozen=True)
class PageSpec:
    """Sizing knobs for paged binary KV caches (validated on
    construction).

    page_size:  tokens per page; must be a positive multiple of the 32-bit
                packing word so V^T packing never straddles pages.
    max_blocks: block-table width for full-attention layers.  The logical
                capacity ``max_blocks * page_size`` replaces the contiguous
                ``max_len`` ring cap — sequences may grow up to it.
    num_pages:  usable arena pages for the full-capacity ring group
                (windowed groups are always fully provisioned at
                ``num_slots * ceil(window / page_size)``).  0 means fully
                provisioned (num_slots * max_blocks).
    """
    page_size: int = 32
    max_blocks: int = 1
    num_pages: int = 0

    def __post_init__(self):
        self.validate()

    @property
    def capacity(self) -> int:
        return self.max_blocks * self.page_size

    def ring_for(self, window: int) -> int:
        """Logical ring length for a layer: its window, or the full
        capacity (window == 0 means full attention)."""
        return min(window or self.capacity, self.capacity)

    def blocks_for_ring(self, ring_len: int) -> int:
        """Block-table width covering ``ring_len`` tokens."""
        return -(-ring_len // self.page_size)

    def arena_pages(self, ring_len: int, num_slots: int) -> int:
        """Usable arena pages for a ring group: ``num_pages`` for the
        contended full-capacity group, fully provisioned (bounded by the
        window) otherwise.  The single source of truth for arena sizing —
        the engine's host-side ``PageArena`` free lists and the per-layer
        device allocations in ``Block.init_cache`` must agree, or page
        ids could run past the device arrays."""
        if self.num_pages and ring_len == self.capacity:
            return self.num_pages
        return num_slots * self.blocks_for_ring(ring_len)

    def validate(self) -> None:
        _check_page_size(self.page_size)
        if self.max_blocks < 1:
            raise ValueError(f"max_blocks must be >= 1, got "
                             f"{self.max_blocks}")
        if self.num_pages and self.num_pages < self.max_blocks:
            raise ValueError(
                f"num_pages ({self.num_pages}) < max_blocks "
                f"({self.max_blocks}): one full-capacity sequence must fit "
                f"the arena or admission deadlocks")


# ---------------------------------------------------------------------------
# Module
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SPSAttention:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    causal: bool = True
    use_rope: bool = True
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    sps_granularity: str = "head"   # layer | head | row
    attn_mode: str = "sps"          # sps | bit_softmax (BiT teacher)
    cross: bool = False             # cross-attention (KV from memory)
    dtype: Any = jnp.float32
    q_chunk: int = Q_CHUNK
    impl: str = "auto"              # deploy matmul impl (projections / M4)
    # deploy attention-score impl (q x k^T, Eq. 7).  "auto" keeps the
    # scores on the packed uint32 words (popcount, with the pad correction
    # ``c = 2*popcount(XNOR) - (d_h + 2*pad)`` in-formula) wherever a slot
    # brings at most 16 queries, and runs a cache-resuming chunk of more
    # as exact int8 matmuls on the MXU (score and context).  Explicit
    # "popcount"/"mxu"/"dense" pin one path at every width as bitwise
    # oracles; tests pin them identical.
    score_impl: str = "auto"
    # decode: read the KV cache grouped by kv head instead of materializing
    # a q-heads-wide repeat (G x less cache-sized intermediate traffic)
    grouped_decode: bool = False
    # O(S*W) sliced-window chunking for static windows (False = dense mask)
    window_chunk: bool = True
    # wo sharding: "row" (all-reduce f32 partials) | "col" (all-gather of
    # packed context bits — 32x less wire)
    wo_partition: str = "row"
    # paged decode: resolve the block table inside the fused Pallas kernel
    # (repro.kernels.paged_attn) so the gathered ring view never
    # materializes; False is the escape hatch — the gather + _attend_cache
    # path, which doubles as the kernel's bitwise reference
    paged_kernel: bool = False

    # -- construction --------------------------------------------------------

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def groups(self) -> int:
        return self.num_heads // self.num_kv_heads

    def _dense(self, in_dim, out_dim, part) -> BinaryDense:
        return BinaryDense(in_dim, out_dim, use_bias=self.qkv_bias and
                           part == "col", partition=part, external_act=True,
                           dtype=self.dtype)

    def init(self, key) -> Params:
        ks = jax.random.split(key, 4)
        h, hkv = self.num_heads, self.num_kv_heads
        p: Params = {
            "wq": self._dense(self.d_model, self.q_dim, "col").init(ks[0]),
            "wk": self._dense(self.d_model, self.kv_dim, "col").init(ks[1]),
            "wv": self._dense(self.d_model, self.kv_dim, "col").init(ks[2]),
            "wo": self._dense(self.q_dim, self.d_model,
                              self.wo_partition).init(ks[3]),
            # shared input binarization (one M1 pass feeds Q/K/V)
            "act_alpha": jnp.ones((), jnp.float32),
            "act_beta": jnp.zeros((), jnp.float32),
            # per-head Q/K/V binarization scales
            "q_alpha": jnp.ones((h,), jnp.float32),
            "q_beta": jnp.zeros((h,), jnp.float32),
            "k_alpha": jnp.ones((hkv,), jnp.float32),
            "k_beta": jnp.zeros((hkv,), jnp.float32),
            "v_alpha": jnp.ones((hkv,), jnp.float32),
            "v_beta": jnp.zeros((hkv,), jnp.float32),
            # context binarization (input to M4)
            "ctx_alpha": jnp.ones((), jnp.float32),
            "ctx_beta": jnp.zeros((), jnp.float32),
            "sps_lambda": self._init_lambda(),
            # BiT teacher's elastic prob scale (bit_softmax mode only)
            "bit_alpha": 0.5 * jnp.ones((h,), jnp.float32),
        }
        return p

    def _init_lambda(self) -> Array:
        if self.sps_granularity == "layer":
            return jnp.zeros((), jnp.float32)
        if self.sps_granularity == "head":
            return jnp.zeros((self.num_heads,), jnp.float32)
        return jnp.zeros((self.num_heads, ROW_TABLE), jnp.float32)

    def specs(self) -> Params:
        # per-head scale/threshold vectors are tiny (H floats) — replicated;
        # head counts (9, 25, ...) need not divide the model axis.
        lam_spec = {"layer": P(), "head": P(None),
                    "row": P(None, None)}[self.sps_granularity]
        return {
            "wq": self._dense(self.d_model, self.q_dim, "col").specs(),
            "wk": self._dense(self.d_model, self.kv_dim, "col").specs(),
            "wv": self._dense(self.d_model, self.kv_dim, "col").specs(),
            "wo": self._dense(self.q_dim, self.d_model,
                              self.wo_partition).specs(),
            "act_alpha": P(), "act_beta": P(),
            "q_alpha": P(None), "q_beta": P(None),
            "k_alpha": P(None), "k_beta": P(None),
            "v_alpha": P(None), "v_beta": P(None),
            "ctx_alpha": P(), "ctx_beta": P(),
            "sps_lambda": lam_spec,
            "bit_alpha": P(None),
        }

    # -- shared helpers ------------------------------------------------------

    def _lambda_for_rows(self, lam: Array, row_idx: Array) -> Array:
        """Resolve the SPS threshold for a block of query rows.
        Returns shape broadcastable to (H, rows, cols)."""
        if self.sps_granularity == "layer":
            return lam[None, None, None]
        if self.sps_granularity == "head":
            return lam[:, None, None]
        idx = jnp.clip(row_idx, 0, ROW_TABLE - 1)
        return lam[:, idx][:, :, None]          # (H, rows, 1)

    def _mask(self, row_idx: Array, col_idx: Array, kv_len,
              window) -> Array:
        """(rows, cols) bool validity mask.  kv_len/window may be traced."""
        r = row_idx[:, None]
        c = col_idx[None, :]
        m = c < kv_len
        if self.causal and not self.cross:
            m = m & (c <= r)
            if window is not None:
                m = m & (c > r - window)
        return m

    def _repeat_kv(self, x: Array) -> Array:
        """(B, Hkv, ...) -> (B, H, ...)."""
        if self.groups == 1:
            return x
        return jnp.repeat(x, self.groups, axis=1)

    # -- QAT face --------------------------------------------------------------

    def qat(self, params: Params, x: Array, *,
            memory: Optional[Array] = None,
            positions: Optional[Array] = None,
            window=None, kv_len=None,
            collect_scores: bool = False
            ) -> Tuple[Array, Dict[str, Array]]:
        """x: (B, S, d).  memory: (B, Skv, d) for cross-attention.
        Returns (out (B, S, d), aux)."""
        b, s, _ = x.shape
        h, hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        xkv = memory if self.cross else x
        skv = xkv.shape[1]
        if positions is None:
            positions = jnp.arange(s)[None, :]

        alpha = jnp.maximum(params["act_alpha"], 1e-6)
        s_x = binarize.sign_ste((x - params["act_beta"]) / alpha)
        if self.cross:
            s_kv = binarize.sign_ste((xkv - params["act_beta"]) / alpha)
        else:
            s_kv = s_x

        wq = self._dense(self.d_model, self.q_dim, "col")
        wk = self._dense(self.d_model, self.kv_dim, "col")
        wv = self._dense(self.d_model, self.kv_dim, "col")
        wo = self._dense(self.q_dim, self.d_model, self.wo_partition)
        q = wq.apply(params["wq"], act_values=s_x, act_scale=alpha)
        k = wk.apply(params["wk"], act_values=s_kv, act_scale=alpha)
        v = wv.apply(params["wv"], act_values=s_kv, act_scale=alpha)
        q = q.reshape(b, s, h, dh)
        k = k.reshape(b, skv, hkv, dh)
        v = v.reshape(b, skv, hkv, dh)
        if self.use_rope and not self.cross:
            q = rope(q, positions, self.rope_theta)
            k = rope(k, positions[:, :skv] if positions.shape[1] >= skv
                     else jnp.arange(skv)[None, :], self.rope_theta)

        # per-head binarization -> +-1 value tensors (B, H*, S, dh)
        def headwise_sign(t, alpha_h, beta_h):
            t = jnp.swapaxes(t, 1, 2)  # (B, H*, S, dh)
            z = (t - beta_h[None, :, None, None]) / \
                jnp.maximum(alpha_h[None, :, None, None], 1e-6)
            return binarize.sign_ste(z)

        s_q = headwise_sign(q, params["q_alpha"], params["q_beta"])
        s_k = headwise_sign(k, params["k_alpha"], params["k_beta"])
        s_v = headwise_sign(v, params["v_alpha"], params["v_beta"])
        s_k = self._repeat_kv(s_k)
        s_v = self._repeat_kv(s_v)
        scale_qk = (params["q_alpha"][:, None, None] *
                    self._repeat_kv(params["k_alpha"][None])[0][:, None, None]
                    / math.sqrt(dh))

        kv_len_ = skv if kv_len is None else kv_len
        lam_all = params["sps_lambda"]
        bit_alpha = params["bit_alpha"]
        mode = self.attn_mode
        aux: Dict[str, Array] = {}

        nchunk = max(1, -(-s // self.q_chunk))
        pad = nchunk * self.q_chunk - s
        s_qp = jnp.pad(s_q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        row_idx_all = jnp.arange(nchunk * self.q_chunk)
        col_idx = jnp.arange(skv)

        # static-window fast path: each q-chunk only touches a
        # (window + chunk)-wide K/V slice — SWA prefill drops from O(S^2)
        # to O(S*W) compute AND traffic (beyond-paper; gemma's traced
        # per-layer windows stay on the dense path)
        kwin = 0
        if (self.window_chunk and isinstance(window, int) and window
                and self.causal and not self.cross and not collect_scores
                and window + self.q_chunk < skv):
            kwin = window + self.q_chunk

        def chunk_body(args):
            s_q_c, rows = args        # (B, H, C, dh), (C,)
            if kwin:
                start = jnp.clip(rows[0] - window, 0, skv - kwin)
                s_k_c = lax.dynamic_slice_in_dim(s_k, start, kwin, axis=2)
                s_v_c = lax.dynamic_slice_in_dim(s_v, start, kwin, axis=2)
                cols = start + jnp.arange(kwin)
            else:
                s_k_c, s_v_c, cols = s_k, s_v, col_idx
            z_int = jnp.einsum("bhcd,bhkd->bhck", s_q_c, s_k_c,
                               preferred_element_type=jnp.float32)
            z = z_int * scale_qk[None]
            m = self._mask(rows, cols, kv_len_, window)[None, None]
            if mode == "bit_softmax":
                zm = jnp.where(m, z, -jnp.inf)
                p = jax.nn.softmax(zm, axis=-1)
                zp = p / jnp.maximum(bit_alpha[None, :, None, None], 1e-6)
                probs = jnp.clip(binarize.round_ste(zp), 0.0, 1.0)
                probs = jnp.where(m, probs, 0.0)
            else:
                lam = self._lambda_for_rows(lam_all, rows)[None]
                probs = sps.sps_ste(z, lam)
                probs = jnp.where(m, probs, 0.0)
            ctx = jnp.einsum("bhck,bhkd->bhcd", probs, s_v_c,
                             preferred_element_type=jnp.float32)
            if collect_scores:
                return ctx, (z, probs)
            return ctx, ()

        chunks_q = s_qp.reshape(b, h, nchunk, self.q_chunk, dh)
        chunks_q = jnp.moveaxis(chunks_q, 2, 0)       # (n, B, H, C, dh)
        rows = row_idx_all.reshape(nchunk, self.q_chunk)
        ctx, extras = lax.map(chunk_body, (chunks_q, rows))
        ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, h, nchunk * self.q_chunk, dh)
        ctx = ctx[:, :, :s]
        if collect_scores:
            z_all = jnp.moveaxis(extras[0], 0, 2)
            z_all = z_all.reshape(b, h, -1, skv)[:, :, :s]
            p_all = jnp.moveaxis(extras[1], 0, 2)
            p_all = p_all.reshape(b, h, -1, skv)[:, :, :s]
            aux["scores"] = z_all
            aux["probs"] = p_all

        # context scale: alpha_v per kv head, broadcast to q heads
        av = self._repeat_kv(params["v_alpha"][None])[0]
        ctx = ctx * av[None, :, None, None]
        # binarize context (signed) -> M4
        ca = jnp.maximum(params["ctx_alpha"], 1e-6)
        s_c = binarize.sign_ste((ctx - params["ctx_beta"]) / ca)
        s_c = jnp.swapaxes(s_c, 1, 2).reshape(b, s, self.q_dim)
        out = wo.apply(params["wo"], act_values=s_c,
                       act_scale=params["ctx_alpha"])
        return out, aux

    # -- deploy: conversion ----------------------------------------------------

    def convert(self, params: Params) -> Params:
        d: Params = {}
        for name, io in (("wq", (self.d_model, self.q_dim, "col")),
                         ("wk", (self.d_model, self.kv_dim, "col")),
                         ("wv", (self.d_model, self.kv_dim, "col")),
                         ("wo", (self.q_dim, self.d_model,
                                 self.wo_partition))):
            d[name] = self._dense(*io).convert(params[name])
        for k in ("act_alpha", "act_beta", "q_alpha", "q_beta", "k_alpha",
                  "k_beta", "v_alpha", "v_beta", "ctx_alpha", "ctx_beta",
                  "sps_lambda"):
            d[k] = params[k]
        return d

    def deploy_specs(self) -> Params:
        d: Params = {}
        for name, io in (("wq", (self.d_model, self.q_dim, "col")),
                         ("wk", (self.d_model, self.kv_dim, "col")),
                         ("wv", (self.d_model, self.kv_dim, "col")),
                         ("wo", (self.q_dim, self.d_model,
                                 self.wo_partition))):
            d[name] = self._dense(*io).deploy_specs()
        for k in ("act_alpha", "act_beta", "ctx_alpha", "ctx_beta"):
            d[k] = P()
        for k in ("q_alpha", "q_beta", "k_alpha", "k_beta", "v_alpha",
                  "v_beta"):
            d[k] = P(None)
        d["sps_lambda"] = {"layer": P(), "head": P(None),
                           "row": P(None, None)}[self.sps_granularity]
        return d

    # -- deploy shared pieces ----------------------------------------------

    def _score_impl(self, rows: int = 0) -> str:
        """Resolve the deploy score-path impl.  'auto' keys on the query
        rows a slot brings (``rows``: C of a cache-resuming chunk), with
        ``rbmm.resolve_impl``'s threshold: up to 16 rows (decode, the
        draft's steps, speculative verify) the scores stay popcount on the
        packed words, which saves the ±1 unpack where the contraction is
        too thin for the MXU; above it ``_chunk_attend`` runs both SPS
        products as exact integer matmuls on the MXU.  Callers that pass
        no rows (the static prefill, decode, memory attention) keep
        popcount."""
        if self.score_impl not in SCORE_IMPLS:
            raise ValueError(f"score_impl must be one of {SCORE_IMPLS}, "
                             f"got {self.score_impl!r}")
        return rbmm.resolve_impl(self.score_impl, rows)

    def _theta_int(self, params: Params) -> Array:
        """Integer SPS thresholds per q-head (or per head-row table)."""
        ak = self._repeat_kv(params["k_alpha"][None])[0]      # (H,)
        scale = (params["q_alpha"] * ak) / math.sqrt(self.head_dim)
        lam = params["sps_lambda"]
        if self.sps_granularity == "layer":
            lam = jnp.broadcast_to(lam, (self.num_heads,))
        if self.sps_granularity == "row":
            return jnp.ceil(lam / jnp.maximum(scale[:, None], 1e-12)
                            ).astype(jnp.int32)               # (H, ROW_TABLE)
        return jnp.ceil(lam / jnp.maximum(scale, 1e-12)).astype(jnp.int32)

    def _theta_rows(self, theta: Array, row_idx: Array) -> Array:
        """Threshold block for query rows -> (H, rows, 1)."""
        if self.sps_granularity == "row":
            idx = jnp.clip(row_idx, 0, ROW_TABLE - 1)
            return theta[:, idx][:, :, None]
        return theta[:, None, None]

    def _project_qkv_deploy(self, params: Params, x: Array, positions: Array
                            ) -> Tuple[Array, Array, Array]:
        """x (B,S,d) -> packed per-head bits:
        q_bits (B,H,S,dhp), k_bits (B,Hkv,S,dhp), s_v values (B,Hkv,S,dh)."""
        b, s, _ = x.shape
        h, hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        wq = self._dense(self.d_model, self.q_dim, "col")
        wk = self._dense(self.d_model, self.kv_dim, "col")
        wv = self._dense(self.d_model, self.kv_dim, "col")
        bits_x = packing.pack_bits((x >= params["act_beta"]).astype(jnp.uint32))
        alpha = params["act_alpha"]
        q = wq.apply_deploy(params["wq"], bits=bits_x, act_alpha=alpha,
                            impl=self.impl).reshape(b, s, h, dh)
        k = wv_k = wk.apply_deploy(params["wk"], bits=bits_x, act_alpha=alpha,
                                   impl=self.impl).reshape(b, s, hkv, dh)
        v = wv.apply_deploy(params["wv"], bits=bits_x, act_alpha=alpha,
                            impl=self.impl).reshape(b, s, hkv, dh)
        del wv_k
        if self.use_rope and not self.cross:
            q = rope(q, positions, self.rope_theta)
            k = rope(k, positions, self.rope_theta)
        # per-head binarize + pack (the data-packing conversion unit)
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        q_bits = packing.pack_bits(
            (qh >= params["q_beta"][None, :, None, None]).astype(jnp.uint32))
        k_bits = packing.pack_bits(
            (kh >= params["k_beta"][None, :, None, None]).astype(jnp.uint32))
        s_v = jnp.where(vh >= params["v_beta"][None, :, None, None], 1.0, -1.0)
        return q_bits, k_bits, s_v

    def _context_scale_heads(self, params: Params) -> Array:
        return self._repeat_kv(params["v_alpha"][None])[0]    # (H,)

    def _output_deploy(self, params: Params, ctx_int: Array) -> Array:
        """ctx_int (B, H, S, dh) int32 -> wo -> (B, S, d) fp."""
        b, h, s, dh = ctx_int.shape
        av = self._context_scale_heads(params)
        ctx = ctx_int.astype(jnp.float32) * av[None, :, None, None]
        s_c_bits = (ctx >= params["ctx_beta"]).astype(jnp.uint32)
        s_c_bits = jnp.swapaxes(s_c_bits, 1, 2).reshape(b, s, self.q_dim)
        wo = self._dense(self.q_dim, self.d_model, self.wo_partition)
        return wo.apply_deploy(params["wo"],
                               bits=packing.pack_bits(s_c_bits),
                               act_alpha=params["ctx_alpha"], impl=self.impl)

    # -- deploy: prefill -----------------------------------------------------

    def deploy_prefill(self, params: Params, x: Array, *,
                       memory: Optional[Array] = None,
                       positions: Optional[Array] = None,
                       window=None,
                       cache_size: int = 0,
                       seq_lens: Optional[Array] = None
                       ) -> Tuple[Array, Optional[KVCache]]:
        """Full-sequence deploy forward.  Returns (out, cache) — cache built
        when cache_size > 0 (ring size W = cache_size).

        ``seq_lens`` (B,) enables ragged right-padded batches: keys at
        columns >= seq_lens[b] are masked out of every real query row, and
        the cache keeps per-sequence ring contents/lengths.  Pad rows still
        compute (they are positionwise garbage) but never leak into real
        rows — attention is the only cross-position mixer and it is masked.
        """
        b, s, _ = x.shape
        h, hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        if positions is None:
            positions = jnp.arange(s)[None, :]
        src = memory if self.cross else x
        if self.cross:
            # project memory with the same shared binarization
            q_bits, _, _ = self._project_qkv_deploy(params, x, positions)
            _, k_bits, s_v = self._project_qkv_deploy(params, src, positions)
        else:
            q_bits, k_bits, s_v = self._project_qkv_deploy(params, x,
                                                           positions)
        skv = src.shape[1]
        k_bits_h = self._repeat_kv(k_bits)
        s_v_h = self._repeat_kv(s_v)
        theta = self._theta_int(params)

        nchunk = max(1, -(-s // self.q_chunk))
        pad = nchunk * self.q_chunk - s
        q_p = jnp.pad(q_bits, ((0, 0), (0, 0), (0, pad), (0, 0)))
        rows_all = jnp.arange(nchunk * self.q_chunk)
        col_idx = jnp.arange(skv)

        # static-window fast path (see qat face): O(S*W) instead of O(S^2)
        kwin = 0
        if (self.window_chunk and isinstance(window, int) and window
                and self.causal and not self.cross
                and window + self.q_chunk < skv):
            kwin = window + self.q_chunk

        def chunk_body(args):
            q_c, rows = args                     # (B,H,C,dhp), (C,)
            if kwin:
                start = jnp.clip(rows[0] - window, 0, skv - kwin)
                k_c = lax.dynamic_slice_in_dim(k_bits_h, start, kwin, axis=2)
                v_c = lax.dynamic_slice_in_dim(s_v_h, start, kwin, axis=2)
                cols = start + jnp.arange(kwin)
            else:
                k_c, v_c, cols = k_bits_h, s_v_h, col_idx
            c = rbmm.rbmm_int(q_c, k_c, dh, scheme="xnor",
                              impl=self._score_impl())  # (B,H,C,Kwin) int32
            th = self._theta_rows(theta, rows)[None]
            probs = (c >= th).astype(jnp.int32)
            m = self._mask(rows, cols, skv, window)[None, None]
            if seq_lens is not None:
                m = m & (cols[None, None, None, :] <
                         seq_lens[:, None, None, None])
            probs = jnp.where(m, probs, 0)
            ctx = jnp.einsum("bhck,bhkd->bhcd", probs.astype(jnp.float32),
                             v_c, preferred_element_type=jnp.float32)
            return ctx.astype(jnp.int32)

        chunks_q = q_p.reshape(b, h, nchunk, self.q_chunk, -1)
        chunks_q = jnp.moveaxis(chunks_q, 2, 0)
        rows = rows_all.reshape(nchunk, self.q_chunk)
        ctx = lax.map(chunk_body, (chunks_q, rows))
        ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, h, -1, dh)[:, :, :s]

        out = self._output_deploy(params, ctx)

        cache = None
        if cache_size:
            w = cache_size
            lens = (jnp.full((b,), s, jnp.int32) if seq_lens is None
                    else jnp.asarray(seq_lens, jnp.int32))
            # Each sequence's last min(len, W) real tokens land at ring
            # slots (t % W).  t spans W consecutive ints per row, so the
            # slot row is a permutation of 0..W-1 — scatters never collide
            # and invalid (t < 0, i.e. len < W) entries hit their own slot
            # with zeros, which is the empty-ring encoding anyway.
            t = lens[:, None] - w + jnp.arange(w)[None, :]      # (B, W)
            valid = t >= 0
            tc = jnp.clip(t, 0, max(s - 1, 0))
            slots = jnp.mod(t, w).astype(jnp.int32)
            kg = jnp.take_along_axis(k_bits, tc[:, None, :, None], axis=2)
            kg = jnp.where(valid[:, None, :, None], kg, jnp.uint32(0))
            kc = jnp.zeros((b, hkv, w, packing.packed_len(dh)), jnp.uint32)
            kc = kc.at[jnp.arange(b)[:, None], :, slots].set(
                jnp.swapaxes(kg, 1, 2))
            # V^T: bit (slot % 32) of word (slot // 32); one-hot word map
            # sums are exact ORs because slots are unique per row
            vg = jnp.take_along_axis(s_v, tc[:, None, :, None], axis=2)
            v_bit = ((vg > 0) & valid[:, None, :, None]).astype(jnp.uint32)
            off = (slots % packing.WORD).astype(jnp.uint32)
            word = slots // packing.WORD
            contrib = jnp.swapaxes(v_bit, 2, 3) << off[:, None, None, :]
            nwords = packing.packed_len(w)
            onehot = (word[:, :, None] == jnp.arange(nwords)[None, None, :]
                      ).astype(jnp.uint32)
            vc = jnp.einsum("bhdt,btw->bhdw", contrib, onehot).astype(
                jnp.uint32)
            cache = KVCache(kc, vc, jnp.minimum(lens, 2**31 - 1))
        return out, cache

    # -- deploy: chunked prefill (cache continuation) -------------------------

    def _chunk_attend(self, params: Params, q_bits: Array, k_bits: Array,
                      s_v: Array, kc_old: Array, vc_old: Array,
                      start: Array, valid: Array, positions: Array,
                      ring, window) -> Array:
        """Attend a chunk of queries over cached prefix + intra-chunk keys.

        q_bits (B,H,C,dhp) are the chunk queries; kc_old/vc_old are the
        packed K/V^T ring view holding the first ``start[b]`` tokens of
        each sequence (ring slot s holds token ``start-1 - ((start-1-s)
        mod ring)``).  k_bits/s_v are the chunk's own K/V.  Because SPS has
        no softmax state the two score blocks combine by plain context
        addition — integer-exact, so chunked == whole-prompt bit-for-bit.
        Chunk rows at/after ``valid[b]`` are pad: they compute garbage for
        their own positions but are masked out of every real row.

        Up to 16 queries per slot (``_score_impl``) the products run as
        packed-word popcount; above that ``_chunk_attend_mxu`` computes
        the same integers as matmuls on the MXU."""
        b, _, c_len, _ = q_bits.shape
        dh = self.head_dim
        w = kc_old.shape[2]
        theta = self._theta_int(params)
        if self.sps_granularity == "row":
            row = jnp.clip(positions, 0, ROW_TABLE - 1)        # (B, C)
            th = jnp.moveaxis(theta[:, row], 0, 1)[..., None]  # (B,H,C,1)
        else:
            th = theta[None, :, None, None]
        # cached prefix: which token each ring slot holds, and whether a
        # query at absolute position p may see it (window in force)
        s_idx = jnp.arange(w)[None, :]
        t_old = start[:, None] - 1 - \
            jnp.mod(start[:, None] - 1 - s_idx, ring)          # (B, W)
        m_pre = ((t_old >= 0) & (s_idx < ring))[:, None, None, :]
        if window:
            m_pre = m_pre & (t_old[:, None, None, :] >
                             positions[:, None, :, None] - window)
        # intra-chunk causal block
        i_idx = jnp.arange(c_len)
        m_in = (i_idx[None, :, None] >= i_idx[None, None, :]) & \
               (i_idx[None, None, :] < valid[:, None, None])
        if window:
            m_in = m_in & (i_idx[None, None, :] >
                           i_idx[None, :, None] - window)
        m_in = m_in[:, None]                                   # (B,1,C,C)
        impl = self._score_impl(c_len)
        if impl == "mxu":
            return self._chunk_attend_mxu(q_bits, k_bits, s_v, kc_old,
                                          vc_old, th, m_pre, m_in)
        kc_h = self._repeat_kv(kc_old)
        with jax.named_scope("sps_score"):
            c_pre = rbmm.rbmm_int(q_bits, kc_h, dh, scheme="xnor",
                                  impl=impl)                   # (B,H,C,W)
        probs_pre = jnp.where(m_pre, (c_pre >= th).astype(jnp.uint32),
                              jnp.uint32(0))
        with jax.named_scope("sps_context"), \
                jax.named_scope("rbmm_popcount"):
            probs_p = packing.pack_bits(probs_pre)             # (B,H,C,W/32)
            nnz = probs_pre.sum(-1, dtype=jnp.int32)
            vc_h = self._repeat_kv(vc_old)
            pc = lax.population_count(
                probs_p[:, :, :, None, :] & vc_h[:, :, None, :, :]
            ).astype(jnp.int32).sum(-1)                        # (B,H,C,dh)
            ctx = 2 * pc - nnz[..., None]
        k_h = self._repeat_kv(k_bits)
        with jax.named_scope("sps_score"):
            c_in = rbmm.rbmm_int(q_bits, k_h, dh, scheme="xnor",
                                 impl=impl)                    # (B,H,C,C)
        probs_in = jnp.where(m_in, (c_in >= th).astype(jnp.int32), 0)
        s_v_h = self._repeat_kv(s_v)
        ctx_in = jnp.einsum("bhck,bhkd->bhcd",
                            probs_in.astype(jnp.float32), s_v_h,
                            preferred_element_type=jnp.float32)
        return ctx + ctx_in.astype(jnp.int32)

    def _chunk_attend_mxu(self, q_bits: Array, k_bits: Array, s_v: Array,
                          kc_old: Array, vc_old: Array, th: Array,
                          m_pre: Array, m_in: Array) -> Array:
        """``_chunk_attend``'s two blocks as int8 matmuls with int32
        accumulation on the MXU: the same integers, bit for bit.

        Query heads are grouped by KV head, (B, Hkv, G, C, dh), so each
        KV head's ring is unpacked to ±1 once and the MXU sees G*C rows.
        Scores are sums of dh products in {-1, +1}, contexts of at most
        W + C products in {-1, 0, +1}, so int32 is exact.  The threshold
        and masks consume the score in place and the probabilities leave
        as {0, 1} int8; probs @ V (±1) gives ``2*popcount - nnz`` of the
        popcount path directly."""
        b, h, c_len, _ = q_bits.shape
        hkv, g, dh = self.num_kv_heads, self.groups, self.head_dim

        def grouped(x):  # (B, H|1, ...) -> (B, Hkv, G, ...) | (B, 1, 1, ...)
            if x.shape[1] == 1:
                return x[:, :, None]
            return x.reshape(x.shape[0], hkv, g, *x.shape[2:])

        def signs(p, k):
            return packing.unpack_signs(p, k, jnp.int8)

        def dot(spec, x, y):
            return jnp.einsum(spec, x, y, preferred_element_type=jnp.int32)

        q = grouped(signs(q_bits, dh))                         # (B,Hkv,G,C,dh)
        th = grouped(th)
        with jax.named_scope("sps_score"), jax.named_scope("rbmm_mxu"):
            c_pre = dot("bhgcd,bhwd->bhgcw", q, signs(kc_old, dh))
            probs_pre = (grouped(m_pre) & (c_pre >= th)).astype(jnp.int8)
            c_in = dot("bhgcd,bhkd->bhgck", q, signs(k_bits, dh))
            probs_in = (grouped(m_in) & (c_in >= th)).astype(jnp.int8)
        v_pre = signs(vc_old, kc_old.shape[2])                 # (B,Hkv,dh,W)
        with jax.named_scope("sps_context"), jax.named_scope("rbmm_mxu"):
            ctx = dot("bhgcw,bhdw->bhgcd", probs_pre, v_pre) + \
                dot("bhgck,bhkd->bhgcd", probs_in, s_v.astype(jnp.int8))
        return ctx.reshape(b, h, c_len, dh)

    def deploy_prefill_chunk(self, params: Params, x: Array, cache, *,
                             window=None, start: Optional[Array] = None,
                             valid_len: Optional[Array] = None
                             ) -> Tuple[Array, Any]:
        """Cache-resuming chunk prefill: x (B, C, d) continues sequences
        whose first ``start[b]`` tokens already live in ``cache``.

        Works on contiguous ``KVCache`` rings and ``PagedKVCache`` block
        tables (pages covering the chunk must already be mapped — the
        engine grows them per chunk).  ``valid_len`` (B,) marks how many
        chunk rows are real; pad rows never write the cache and never leak
        into real rows, so a fixed chunk width serves every prompt length
        with ONE compiled shape.  The attend runs BEFORE the ring write:
        writing first would let a wrapping chunk overwrite prefix tokens
        still inside earlier rows' windows.  Returns (out (B,C,d),
        updated cache with ``length = start + valid_len``)."""
        if self.cross:
            raise ValueError("chunked prefill is causal self-attention "
                             "only (cross-attention memory is static)")
        b, c_len, _ = x.shape
        if start is None:
            start = cache.length
        start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
        if valid_len is None:
            valid = jnp.full((b,), c_len, jnp.int32)
        else:
            valid = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32),
                                     (b,))
        positions = start[:, None] + jnp.arange(c_len)[None, :]
        q_bits, k_bits, s_v = self._project_qkv_deploy(params, x, positions)
        kc_old, vc_old, ring = self._cache_ring_view(cache)
        ctx_int = self._chunk_attend(params, q_bits, k_bits, s_v, kc_old,
                                     vc_old, start, valid, positions, ring,
                                     window)
        out = self._output_deploy(params, ctx_int)
        return out, self._write_chunk(cache, k_bits, s_v, start, valid)

    def _cache_ring_view(self, cache) -> Tuple[Array, Array, Any]:
        """(kc, vc, ring) packed K / V^T ring view of a decode cache —
        the contiguous arrays themselves, or the block-table gather of a
        ``PagedKVCache`` laid out so logical ring slot s is column s."""
        if not isinstance(cache, PagedKVCache):
            return cache.k_bits, cache.vt_bits, cache.k_bits.shape[2]
        b = cache.block_table.shape[0]
        hkv, dh = self.num_kv_heads, self.head_dim
        page = cache.k_pages.shape[2]
        w = cache.block_table.shape[1] * page
        with jax.named_scope("paged_gather"):
            bt = jnp.clip(cache.block_table, 0, cache.k_pages.shape[0] - 1)
            kc = jnp.moveaxis(cache.k_pages[bt], 1, 2).reshape(b, hkv, w, -1)
            vc = jnp.moveaxis(cache.vt_pages[bt], 1, 3
                              ).reshape(b, hkv, dh, w // packing.WORD)
        return kc, vc, cache.ring_len

    def _write_chunk(self, cache, k_bits: Array, s_v: Array, start: Array,
                     valid: Array):
        """Commit chunk K/V into the ring (select, last-writer-wins).

        ``k_bits`` (B,Hkv,C,dhp) / ``s_v`` (B,Hkv,C,dh) are the chunk's
        projections; slot s takes chunk token t_new = largest t <
        start+valid with t % ring == s, IF that token is the chunk's
        (>= start); all other slots keep their old contents.  Rows past
        ``valid[b]`` never write — a row with valid == 0 writes NOTHING
        and keeps its previous length, which is what lets the
        speculative-verify path commit a per-sequence accepted prefix
        (and lets inactive pool slots ride through untouched).  Returns
        the updated cache with ``length = start + valid`` where any
        token was written."""
        b, _, c_len, _ = k_bits.shape
        paged = isinstance(cache, PagedKVCache)
        if paged:
            page = cache.k_pages.shape[2]
            ring = cache.ring_len
            w = cache.block_table.shape[1] * page
            _, vc_old, _ = self._cache_ring_view(cache)
        else:
            w = cache.k_bits.shape[2]
            ring = w
            vc_old = cache.vt_bits
        lv = start + valid
        # rows that commit nothing keep their previous per-sequence length
        new_len = jnp.where(valid > 0, lv, cache.length).astype(jnp.int32)
        s_all = jnp.arange(w)
        t_new = lv[:, None] - 1 - jnp.mod(lv[:, None] - 1 - s_all[None, :],
                                          ring)                # (B, W)
        wr = (t_new >= start[:, None]) & (t_new >= 0) & \
             (s_all[None, :] < ring) & (valid[:, None] > 0)
        j = jnp.clip(t_new - start[:, None], 0, c_len - 1)
        kg = jnp.take_along_axis(k_bits, j[:, None, :, None],
                                 axis=2)                       # (B,Hkv,W,dhp)
        v_bit = jnp.swapaxes(
            jnp.take_along_axis(s_v, j[:, None, :, None], axis=2) > 0,
            2, 3)                                              # (B,Hkv,dh,W)
        wr_words = packing.pack_bits(wr.astype(jnp.uint32))    # (B, W/32)
        new_words = packing.pack_bits(
            (v_bit & wr[:, None, None, :]).astype(jnp.uint32))
        if not paged:
            kc = jnp.where(wr[:, None, :, None], kg, cache.k_bits)
            vc = (cache.vt_bits & ~wr_words[:, None, None, :]) | new_words
            return KVCache(kc, vc, new_len)
        # paged: scatter written slots/words through the block table;
        # unwritten positions route to the trash page 0 (page_size % 32
        # keeps whole V^T words inside one page)
        lp = s_all // page
        off2 = jnp.broadcast_to((s_all % page)[None], (b, w))
        phys = jnp.take_along_axis(cache.block_table,
                                   jnp.broadcast_to(lp[None], (b, w)),
                                   axis=1)
        phys = jnp.where(wr, phys, 0)
        kp = cache.k_pages.at[phys, :, off2].set(jnp.swapaxes(kg, 1, 2))
        wp_n = w // packing.WORD
        j32 = jnp.arange(wp_n) * packing.WORD
        wj2 = jnp.broadcast_to(((j32 % page) // packing.WORD)[None],
                               (b, wp_n))
        physw = jnp.take_along_axis(cache.block_table,
                                    jnp.broadcast_to((j32 // page)[None],
                                                     (b, wp_n)), axis=1)
        physw = jnp.where(wr_words != 0, physw, 0)
        merged = (vc_old & ~wr_words[:, None, None, :]) | new_words
        vp = cache.vt_pages.at[physw, :, :, wj2].set(
            jnp.moveaxis(merged, 3, 1))
        return cache._replace(k_pages=kp, vt_pages=vp, length=new_len)

    # -- deploy: speculative verify (attend-only) + deferred commit ----------

    def deploy_verify_chunk(self, params: Params, x: Array, cache, *,
                            window=None, start: Optional[Array] = None,
                            valid: Optional[Array] = None
                            ) -> Tuple[Array, Tuple[Array, Array]]:
        """Score a candidate chunk WITHOUT writing the cache.

        x (B, C, d) holds the pending token + the drafted tokens of each
        sequence; the attend is the same prefix-plus-intra-block path as
        ``deploy_prefill_chunk``, but the ring write is deferred: the
        method returns (out, (k_bits, s_v)) so the caller can decide per
        sequence how many leading positions to commit (``commit_chunk``)
        once acceptance is known.  Never touching the cache before
        acceptance is what makes speculative rollback exact even on
        wrapped SWA rings, where a write destroys the evicted token
        irrecoverably.

        ``valid`` (B,) marks the real leading positions per row (default:
        all C).  Real queries sit before ``valid`` so causal masking
        already hides the garbage tail from them; passing ``valid``
        additionally zeroes garbage keys out of the intra-chunk score
        block, letting prefill-chunk rows share a pooled verify forward
        with decode rows (their committed outputs stay bit-identical to
        ``deploy_prefill_chunk``)."""
        if self.cross:
            raise ValueError("speculative verify is causal self-attention "
                             "only (cross-attention memory is static)")
        b, c_len, _ = x.shape
        if start is None:
            start = cache.length
        start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
        if valid is None:
            valid = jnp.full((b,), c_len, jnp.int32)
        else:
            valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (b,))
        positions = start[:, None] + jnp.arange(c_len)[None, :]
        q_bits, k_bits, s_v = self._project_qkv_deploy(params, x, positions)
        kc_old, vc_old, ring = self._cache_ring_view(cache)
        ctx_int = self._chunk_attend(params, q_bits, k_bits, s_v, kc_old,
                                     vc_old, start, valid, positions, ring,
                                     window)
        return self._output_deploy(params, ctx_int), (k_bits, s_v)

    def commit_chunk(self, cache, proj: Tuple[Array, Array], start: Array,
                     n_commit: Array):
        """Write the first ``n_commit[b]`` positions of a verified chunk
        (projections from ``deploy_verify_chunk``) at offset ``start[b]``.
        Rows with n_commit == 0 are untouched (content AND length)."""
        k_bits, s_v = proj
        b = k_bits.shape[0]
        start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
        n_commit = jnp.broadcast_to(jnp.asarray(n_commit, jnp.int32), (b,))
        return self._write_chunk(cache, k_bits, s_v, start, n_commit)

    # -- deploy: cross-attention memory ---------------------------------------

    def build_memory_cache(self, params: Params, memory: Array) -> KVCache:
        """Project encoder output once into binary K / V^T caches (cross)."""
        b, s, _ = memory.shape
        positions = jnp.arange(s)[None, :]
        _, k_bits, s_v = self._project_qkv_deploy(params, memory, positions)
        vt = packing.pack_bits(
            (jnp.swapaxes(s_v, 2, 3) > 0).astype(jnp.uint32))
        return KVCache(k_bits, vt, jnp.full((b,), s, jnp.int32))

    def attend_memory(self, params: Params, x: Array, mem: KVCache) -> Array:
        """Cross-attention of x (B, S, d) over a static memory cache
        (read-only; no causal mask).  Fully binary score+context path."""
        b, s, _ = x.shape
        h, dh = self.num_heads, self.head_dim
        positions = jnp.arange(s)[None, :]
        q_bits, _, _ = self._project_qkv_deploy(params, x, positions)
        kc_h = self._repeat_kv(mem.k_bits)
        c = rbmm.rbmm_int(q_bits, kc_h, dh, scheme="xnor",
                          impl=self._score_impl())
        theta = self._theta_int(params)
        if self.sps_granularity == "row":
            th = self._theta_rows(theta, jnp.clip(positions[0], 0,
                                                  ROW_TABLE - 1))[None]
        else:
            th = theta[None, :, None, None]
        probs = (c >= th).astype(jnp.uint32)
        skv = mem.k_bits.shape[2]
        mlen = jnp.reshape(jnp.asarray(mem.length), (-1, 1))  # (B|1, 1)
        valid = (jnp.arange(skv)[None, :] < mlen)[:, None, None, :]
        probs = jnp.where(valid, probs, jnp.uint32(0))
        probs_p = packing.pack_bits(probs)
        vc_h = self._repeat_kv(mem.vt_bits)
        pc = lax.population_count(
            probs_p[:, :, :, None, :] & vc_h[:, :, None, :, :]
        ).astype(jnp.int32).sum(-1)
        nnz = probs.sum(-1, dtype=jnp.int32)
        ctx_int = 2 * pc - nnz[..., None]                     # (B,H,S,dh)
        return self._output_deploy(params, ctx_int)

    # -- deploy: decode --------------------------------------------------------

    def init_cache(self, batch: int, max_len: int) -> KVCache:
        hkv, dh = self.num_kv_heads, self.head_dim
        return KVCache(
            jnp.zeros((batch, hkv, max_len, packing.packed_len(dh)),
                      jnp.uint32),
            jnp.zeros((batch, hkv, dh, packing.packed_len(max_len)),
                      jnp.uint32),
            jnp.zeros((batch,), jnp.int32),
        )

    def init_paged_cache(self, batch: int, *, ring_len: int, page_size: int,
                         num_blocks: int, num_pages: int) -> PagedKVCache:
        """Build an empty page arena + block tables for this layer.

        ``num_pages`` usable pages are allocated plus the reserved trash
        page 0.  ``ring_len`` is the logical ring length (the window for
        SWA layers); ``num_blocks`` must cover it."""
        _check_page_size(page_size)
        if num_blocks * page_size < ring_len:
            raise ValueError(f"{num_blocks} blocks of {page_size} cannot "
                             f"cover ring_len={ring_len}")
        hkv, dh = self.num_kv_heads, self.head_dim
        return PagedKVCache(
            k_pages=jnp.zeros((num_pages + 1, hkv, page_size,
                               packing.packed_len(dh)), jnp.uint32),
            vt_pages=jnp.zeros((num_pages + 1, hkv, dh,
                                page_size // packing.WORD), jnp.uint32),
            block_table=jnp.zeros((batch, num_blocks), jnp.int32),
            length=jnp.zeros((batch,), jnp.int32),
            ring_len=jnp.int32(ring_len),
        )

    def _attend_cache(self, params: Params, q_bits: Array, kc: Array,
                      vc: Array, pos: Array, valid: Array) -> Array:
        """Shared decode attend: one query token per sequence against a
        packed K (B,Hkv,W,dhp) / V^T (B,Hkv,dh,W/32) view.  ``valid``
        (B, W) masks live ring slots; ``pos`` (B,) selects the SPS row
        threshold.  Fully binary score+context path (Eq. 7 xnor then
        and_dc), identical math for the contiguous and paged layouts."""
        b = q_bits.shape[0]
        h, hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        w = kc.shape[2]
        with jax.named_scope("sps_score"):
            if self.grouped_decode and self.groups > 1:
                g = self.groups
                qg = q_bits[:, :, 0].reshape(b, hkv, g, -1)   # (B,Hkv,G,dhp)
                # xnor_popcount_score carries the Eq. 7 pad correction
                # (-(d_h + 2*pad)); the old inline ``2*pc - dh`` silently
                # dropped it, shifting every score for d_h % 32 != 0
                # (pinned in tests/test_models_deploy.py)
                c = packing.xnor_popcount_score(
                    qg[:, :, :, None, :], kc[:, :, None, :, :], dh
                ).reshape(b, h, 1, w)                         # (B,H,1,W)
            else:
                kc_h = self._repeat_kv(kc)                    # (B,H,W,dhp)
                c = rbmm.rbmm_int(q_bits, kc_h, dh, scheme="xnor",
                                  impl=self._score_impl())    # (B,H,1,W)
        theta = self._theta_int(params)
        if self.sps_granularity == "row":
            row = jnp.clip(pos, 0, ROW_TABLE - 1)             # (B,)
            th = theta[:, row].T[:, :, None, None]            # (B,H,1,1)
        else:
            th = theta[None, :, None, None]
        probs = (c >= th).astype(jnp.uint32)
        probs = jnp.where(valid[:, None, None, :], probs, jnp.uint32(0))
        # pack probs along W -> and_dc against V^T (fully binary M3)
        probs_p = packing.pack_bits(probs)                    # (B,H,1,W/32)
        nnz = probs.sum(-1, dtype=jnp.int32)                  # (B,H,1)
        if self.grouped_decode and self.groups > 1:
            g = self.groups
            pg = probs_p[:, :, 0].reshape(b, hkv, g, -1)      # (B,Hkv,G,Wp)
            x = pg[:, :, :, None, :] & vc[:, :, None, :, :]   # (B,Hkv,G,dh,Wp)
            pc = lax.population_count(x).astype(jnp.int32).sum(-1)
            pc = pc.reshape(b, h, 1, dh)
        else:
            vc_h = self._repeat_kv(vc)                        # (B,H,dh,W/32)
            pc = lax.population_count(
                probs_p[:, :, :, None, :] & vc_h[:, :, None, :, :]
            ).astype(jnp.int32).sum(-1)                       # (B,H,1,dh)
        ctx_int = 2 * pc - nnz[..., None]
        return self._output_deploy(params, ctx_int)

    def deploy_decode(self, params: Params, x: Array, cache, *,
                      window=None) -> Tuple[Array, Any]:
        """x: (B, 1, d) one new token; cache ring size W.
        Fully binary score+context path (Eq. 7 xnor then and_dc).

        Every sequence in the batch advances from its OWN ``cache.length``
        — ring slot, RoPE position, validity mask and SPS row threshold are
        all per-sequence, so a slot pool can decode requests admitted at
        different times in one step.  A ``PagedKVCache`` takes the paged
        path (same math through a block-table gather); ``window`` is
        enforced structurally in both layouts — the logical ring length
        equals the window for SWA archs, so evicted tokens are simply
        overwritten."""
        del window
        if isinstance(cache, PagedKVCache):
            return self._deploy_decode_paged(params, x, cache)
        b, _, _ = x.shape
        w = cache.k_bits.shape[2]
        # per-sequence token position (legacy scalar lengths broadcast)
        pos = jnp.broadcast_to(jnp.asarray(cache.length, jnp.int32), (b,))
        positions = pos[:, None]
        q_bits, k_bits_new, s_v_new = self._project_qkv_deploy(
            params, x, positions)               # (B,H,1,dhp), (B,Hkv,1,dhp)

        barange = jnp.arange(b)
        slot = (pos % w).astype(jnp.int32)                    # (B,)
        kc = cache.k_bits.at[barange, :, slot].set(k_bits_new[:, :, 0])
        # V^T ring update: set bit (slot % 32) of word (slot // 32)
        word_i = slot // packing.WORD
        off = (slot % packing.WORD).astype(jnp.uint32)
        v_bit = (s_v_new[:, :, 0] > 0).astype(jnp.uint32)     # (B,Hkv,dh)
        old = cache.vt_bits[barange, :, :, word_i]            # (B,Hkv,dh)
        mask_bit = (jnp.uint32(1) << off)[:, None, None]
        new = (old & ~mask_bit) | (v_bit << off[:, None, None])
        vc = cache.vt_bits.at[barange, :, :, word_i].set(new)

        valid = jnp.arange(w)[None, :] <= pos[:, None]        # (B,W)
        out = self._attend_cache(params, q_bits, kc, vc, pos, valid)
        return out, KVCache(kc, vc, pos + 1)

    def _deploy_decode_paged(self, params: Params, x: Array,
                             cache: PagedKVCache
                             ) -> Tuple[Array, PagedKVCache]:
        """Paged decode step: write the new K/V^T bits through the block
        table, then attend over the gathered page view.

        The gathered view is laid out so logical ring slot s lands at
        column s (page s // page_size owns columns [page*`s//page_size`,
        ...)), making the math bit-identical to a contiguous ring of the
        same logical length — the extra gathered columns past ``ring_len``
        are masked off."""
        b, _, _ = x.shape
        hkv, dh = self.num_kv_heads, self.head_dim
        page = cache.k_pages.shape[2]
        nblk = cache.block_table.shape[1]
        wg = nblk * page                                      # gathered width
        ring = cache.ring_len                                 # () int32
        pos = jnp.broadcast_to(jnp.asarray(cache.length, jnp.int32), (b,))
        q_bits, k_bits_new, s_v_new = self._project_qkv_deploy(
            params, x, pos[:, None])
        # logical ring slot -> (physical page, in-page offset)
        slot = (pos % ring).astype(jnp.int32)                 # (B,)
        lp = slot // page
        off = slot % page
        barange = jnp.arange(b)
        phys = cache.block_table[barange, lp]                 # (B,)
        # free pool slots have block_table rows of 0 -> their garbage
        # decode writes land on the reserved trash page, never on live data
        kp = cache.k_pages.at[phys, :, off].set(k_bits_new[:, :, 0])
        word_i = off // packing.WORD
        bit = (off % packing.WORD).astype(jnp.uint32)
        v_bit = (s_v_new[:, :, 0] > 0).astype(jnp.uint32)     # (B,Hkv,dh)
        old = cache.vt_pages[phys, :, :, word_i]              # (B,Hkv,dh)
        mask_bit = (jnp.uint32(1) << bit)[:, None, None]
        new = (old & ~mask_bit) | (v_bit << bit[:, None, None])
        vp = cache.vt_pages.at[phys, :, :, word_i].set(new)
        if self.paged_kernel:
            # fused path: the kernel resolves the block table in its grid
            # index map and attends over packed pages directly — the
            # gathered ring view below never materializes
            theta = self._theta_int(params)
            if self.sps_granularity == "row":
                row = jnp.clip(pos, 0, ROW_TABLE - 1)         # (B,)
                th_b = theta[:, row].T                        # (B, H)
            else:
                th_b = jnp.broadcast_to(theta[None, :],
                                        (b, self.num_heads))
            ctx_int = paged_attn_ops.paged_gather_decode(
                q_bits[:, :, 0], kp, vp, cache.block_table, pos,
                ring, th_b, d_h=dh)
            out = self._output_deploy(params, ctx_int[:, :, None, :])
            return out, cache._replace(k_pages=kp, vt_pages=vp,
                                       length=pos + 1)
        # gather the slot's pages into a contiguous-ring view
        with jax.named_scope("paged_gather"):
            bt = jnp.clip(cache.block_table, 0, kp.shape[0] - 1)  # (B,nblk)
            kc = kp[bt]                               # (B,nblk,Hkv,page,dhp)
            kc = jnp.moveaxis(kc, 1, 2).reshape(b, hkv, wg, -1)
            vc = vp[bt]                               # (B,nblk,Hkv,dh,p32)
            vc = jnp.moveaxis(vc, 1, 3)               # (B,Hkv,dh,nblk,p32)
            vc = vc.reshape(b, hkv, dh, wg // packing.WORD)
        cols = jnp.arange(wg)[None, :]
        valid = (cols <= pos[:, None]) & (cols < ring)        # (B,Wg)
        out = self._attend_cache(params, q_bits, kc, vc, pos, valid)
        return out, cache._replace(k_pages=kp, vt_pages=vp, length=pos + 1)
