"""RBMM — real 1-bit binary matrix multiplication (paper §III-B).

Implements Eq. 7 on packed uint32 datapacks:

  signed   x signed  ("xnor")  : a.b = 2*popcount(XNOR(a, b)) - K
  unsigned x signed  ("and_dc"): a.b = 2*popcount(AND(a, b))  - K + delta

where delta is the "don't-care" count (number of 0-elements of the unsigned
operand within the true K region).  Both schemes share one engine; Eq. 8
compositionality (split-K additivity) lets the same code serve per-head (d_h),
full-width (d) and FFN (R*d) contractions — that is the paper's PE-reuse story
and here it is simply shape polymorphism.

Execution paths (``impl``):

  popcount : packed uint32 VPU arithmetic (paper-faithful).  jnp-level body
             here; the Pallas TPU kernel lives in ``repro.kernels.rbmm``.
  mxu      : beyond-paper TPU adaptation — operands stay packed in HBM (32x
             bandwidth/memory win), are unpacked to +-1 bf16 tiles on the fly
             and fed to the MXU.  Exact: |acc| <= K < 2^24 in f32.
             The Pallas fused version lives in ``repro.kernels.rbmm_mxu``.
  dense    : unpack to float and matmul (oracle / GPU-baseline analogue).
  auto     : decode-shaped (M small, memory-bound) -> popcount;
             train/prefill (compute-bound) -> mxu.

Quantization fusion (Eq. 9/10): ``rbmm_binary`` emits the *next layer's packed
bits directly* from the integer accumulator via one threshold compare
``c >= theta`` — no intermediate integer matrix ever reaches HBM — and returns
the DC RETURN vector needed by a downstream {0,1}-scheme RBMM.

FFN blocking (Eq. 11): ``ffn_blocked`` computes ReLU(X Y) Z as a sum of R
rank-d blocks with two l x d live buffers instead of one l x FF buffer.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import packing

Array = jax.Array

SCHEMES = ("xnor", "and_dc")
IMPLS = ("popcount", "mxu", "dense", "auto")

# Rows-per-block when blocking the popcount broadcast to bound the (virtual)
# (M, P, Kp) intermediate.  XLA fuses xor/popcount into the reduction, so this
# mostly shapes the loop structure, not real memory.
_POPCOUNT_BLOCK_M = 512


def _check(scheme: str, impl: str) -> None:
    if scheme not in SCHEMES:
        raise ValueError(f"scheme must be one of {SCHEMES}, got {scheme!r}")
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")


def resolve_impl(impl: str, m: int) -> str:
    """'auto' dispatch: small-M (decode GEMV, memory-bound) -> popcount,
    large-M (train/prefill, compute-bound) -> mxu."""
    if impl != "auto":
        return impl
    return "popcount" if m <= 16 else "mxu"


# ---------------------------------------------------------------------------
# Integer RBMM (Eq. 7)
# ---------------------------------------------------------------------------


def _bitop_popcount_sum(a: Array, b: Array, scheme: str) -> Array:
    """sum_w popcount(op(a_w, b_w)) over the packed axis.

    a: (..., M, Kp) uint32;  b: (..., P, Kp) uint32  ->  (..., M, P) int32.
    Broadcast-xor/and + popcount + reduce; XLA fuses the producer into the
    reduction so the (M, P, Kp) tensor is virtual.
    """
    aa = a[..., :, None, :]
    bb = b[..., None, :, :]
    if scheme == "xnor":
        x = ~(aa ^ bb)
    else:  # and_dc
        x = aa & bb
    return lax.population_count(x).astype(jnp.int32).sum(axis=-1)


def _rbmm_int_popcount(a: Array, b: Array, k: int, scheme: str,
                       dc: Optional[Array]) -> Array:
    kp = a.shape[-1]
    pad_bits = kp * packing.WORD - k
    if scheme == "xnor":
        # Unified pad convention: BOTH operands pad with 0 (the pack_bits
        # default).  Each pad bit then contributes XNOR(0,0)=1 to the
        # popcount, a static constant folded into the -K term:
        #   c_true = 2*(pc - pad) - k
        pc = _bitop_popcount_sum(a, b, "xnor")
        return 2 * pc - jnp.int32(k + 2 * pad_bits)
    # and_dc: A pads 0 -> AND pad bits 0.  delta over true K region.
    if dc is None:
        dc = packing.dc_count(a, k)  # (..., M)
    pc = _bitop_popcount_sum(a, b, "and_dc")
    return 2 * pc - jnp.int32(k) + dc[..., :, None].astype(jnp.int32)


def _unpack_operand(p: Array, k: int, scheme_side: str,
                    dtype=jnp.bfloat16) -> Array:
    """Unpack (..., M, Kp) words -> (..., M, K) values.
    scheme_side 'signed' -> +-1, 'unsigned' -> {0,1}."""
    bits = packing.unpack_bits(p, k)
    if scheme_side == "signed":
        return (2 * bits - 1).astype(dtype)
    return bits.astype(dtype)


def _rbmm_int_mxu(a: Array, b: Array, k: int, scheme: str) -> Array:
    """Unpack-to-bf16 + MXU matmul.  Exact for k < 2^24 (f32 accum)."""
    a_side = "signed" if scheme == "xnor" else "unsigned"
    av = _unpack_operand(a, k, a_side)
    bv = _unpack_operand(b, k, "signed")
    out = jnp.einsum("...mk,...pk->...mp", av, bv,
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)


def rbmm_int(a: Array, b: Array, k: int, *, scheme: str = "xnor",
             dc: Optional[Array] = None, impl: str = "popcount") -> Array:
    """Integer RBMM on packed operands.

    a: (..., M, Kp) uint32 — rows packed along K (LSB-first).
       xnor scheme: bits encode {-1 -> 0, +1 -> 1}.
       and_dc scheme: bits encode {0 -> 0, 1 -> 1} (unsigned operand).
    b: (..., P, Kp) uint32 — *columns* of the logical (K, P) matrix, packed
       along K.  Always signed {-1,+1} encoding (weights / K / V).
    k: true contraction length (pre-packing).
    dc: optional precomputed don't-care counts (..., M) for and_dc — the
        "DC INPUT" the paper streams from the previous engine invocation.
    Returns (..., M, P) int32, exactly ``unpacked(a) @ unpacked(b).T``.
    """
    _check(scheme, impl)
    impl = resolve_impl(impl, a.shape[-2])
    # the path's ops carry ``rbmm_<impl>`` in their op metadata, so a
    # profile can key per-path kernel time on the scope
    with jax.named_scope(f"rbmm_{impl}"):
        if impl in ("mxu", "dense"):
            # the MXU path computes the true dot directly: no dc needed
            return _rbmm_int_mxu(a, b, k, scheme)
        return _rbmm_int_popcount(a, b, k, scheme, dc)


# ---------------------------------------------------------------------------
# Quantization-fused RBMM (Eq. 9/10)
# ---------------------------------------------------------------------------


def rbmm_binary(a: Array, b: Array, k: int, theta: Array, *,
                scheme: str = "xnor", dc: Optional[Array] = None,
                impl: str = "popcount",
                return_dc: bool = False,
                pack_output: bool = True
                ) -> Tuple[Array, Optional[Array]]:
    """Quantization-fused RBMM: bits_j = (c_j >= theta_j), Eq. 10.

    theta: (P,) or broadcastable to (..., M, P) — the fused integer threshold
    (scales, shifts, ReLU and the Eq. 7 ``-K`` constant all folded in by the
    caller via ``repro.core.binarize.fused_threshold``).

    Returns (bits, dc_return):
      bits: packed (..., M, ceil(P/32)) uint32 if pack_output else
            (..., M, P) uint32 in {0,1}.
      dc_return: (..., M) int32 count of zeros among the P outputs (the
            paper's DC RETURN, consumed as DC INPUT by a following and_dc
            RBMM) if return_dc else None.
    """
    c = rbmm_int(a, b, k, scheme=scheme, dc=dc, impl=impl)
    bits = (c >= theta).astype(jnp.uint32)
    dc_out = None
    if return_dc:
        p = bits.shape[-1]
        dc_out = jnp.int32(p) - bits.sum(axis=-1, dtype=jnp.int32)
    if pack_output:
        bits = packing.pack_bits(bits)
    return bits, dc_out


# ---------------------------------------------------------------------------
# Split-K compositionality (Eq. 8) — used by tests and the kernels' grids
# ---------------------------------------------------------------------------


def rbmm_int_split_k(a: Array, b: Array, k: int, splits: int, *,
                     scheme: str = "xnor", dc: Optional[Array] = None) -> Array:
    """Reference implementation of Eq. 8: partial RBVMs over S word-chunks
    accumulate to the full result.  Exact for any splits dividing Kp."""
    kp = a.shape[-1]
    if kp % splits:
        raise ValueError(f"splits={splits} must divide packed len {kp}")
    step = kp // splits
    total = None
    for s in range(splits):
        a_s = a[..., s * step:(s + 1) * step]
        b_s = b[..., s * step:(s + 1) * step]
        k_s = min(step * packing.WORD, k - s * step * packing.WORD)
        dc_s = None
        if scheme == "and_dc":
            dc_s = packing.dc_count(a_s, k_s)
        part = rbmm_int(a_s, b_s, k_s, scheme=scheme, dc=dc_s)
        total = part if total is None else total + part
    return total


# ---------------------------------------------------------------------------
# Blocked FFN (Eq. 11)
# ---------------------------------------------------------------------------


def ffn_blocked(x: Array, y: Array, z: Array, k: int, theta1: Array,
                r: int, *, impl: str = "popcount") -> Array:
    """E = ReLU(X Y) Z  as  sum_r ReLU(X Y_r) Z_r   (Eq. 11).

    x: (..., M, Kp) packed signed activations (K = d).
    y: (FF, Kp) packed signed W1 columns (FF = R*d).
    z: (D, FFp_r-chunk) — we pass z pre-split: (R, D, d/32) packed signed W2
       columns, each chunk contracting over d of the FF dimension.
    theta1: (FF,) fused unsigned+ReLU thresholds for the first layer.
    Returns (..., M, D) int32 accumulated over R blocks — two live buffers of
    size l x d, never l x FF (the paper's memory optimization; here it bounds
    the VMEM working set).
    """
    _check("xnor", impl)
    ff = y.shape[-2]
    if ff % r:
        raise ValueError(f"R={r} must divide FF={ff}")
    d_blk = ff // r

    def body(s, acc):
        y_s = lax.dynamic_slice_in_dim(y, s * d_blk, d_blk, axis=-2)
        th_s = lax.dynamic_slice_in_dim(theta1, s * d_blk, d_blk, axis=-1)
        h_bits, h_dc = rbmm_binary(x, y_s, k, th_s, scheme="xnor",
                                   impl=impl, return_dc=True,
                                   pack_output=True)
        z_s = z[s]
        part = rbmm_int(h_bits, z_s, d_blk, scheme="and_dc", dc=h_dc,
                        impl=impl)
        return acc + part

    m = x.shape[:-1]
    d_out = z.shape[-2]
    acc0 = jnp.zeros(m + (d_out,), jnp.int32)
    return lax.fori_loop(0, r, body, acc0)


def split_w2_for_blocked_ffn(w2_packed_by_chunk: Array) -> Array:
    """Identity helper documenting the expected Z layout: (R, D, d//32)."""
    return w2_packed_by_chunk


# ---------------------------------------------------------------------------
# Mode wrappers — explicit correspondence to the paper's M1-M4 / F1-F2
# ---------------------------------------------------------------------------


def mode_m1_qkv(x: Array, w: Array, k: int, theta: Array, *,
                impl: str = "popcount") -> Array:
    """M1: Q/K/V projection (l x d x d), quantized binary output."""
    bits, _ = rbmm_binary(x, w, k, theta, scheme="xnor", impl=impl)
    return bits


def mode_m2_score(q: Array, kmat: Array, d_h: int, lam_theta: Array, *,
                  mask: Optional[Array] = None,
                  impl: str = "popcount") -> Tuple[Array, Array]:
    """M2: attention scores (h, l, d_h) x (h, d_h, l) -> SPS bits + DC HEADs.

    lam_theta is the SPS threshold *pre-scaled to integer domain*
    (theta = ceil(lambda * sqrt(d_h) ... ) folded by repro.core.sps).
    mask: optional additive boolean mask (True = masked out -> bit 0); the
    paper applies causal/padding masks by index comparison in the same pass.
    Returns (bits (..., h, l, l) unpacked, dc (..., h, l)); unpacked because
    M3 consumes rows immediately (packing optional there).
    """
    c = rbmm_int(q, kmat, d_h, scheme="xnor", impl=impl)
    bits = (c >= lam_theta).astype(jnp.uint32)
    if mask is not None:
        bits = jnp.where(mask, jnp.uint32(0), bits)
    l = bits.shape[-1]
    dc = jnp.int32(l) - bits.sum(axis=-1, dtype=jnp.int32)
    return bits, dc


def mode_m3_context(probs_packed: Array, v_t: Array, l: int, dc: Array,
                    theta: Array, *, impl: str = "popcount") -> Array:
    """M3: context = probs ({0,1}) x V^T -> quantized binary output bits."""
    bits, _ = rbmm_binary(probs_packed, v_t, l, theta, scheme="and_dc",
                          dc=dc, impl=impl)
    return bits


def mode_m4_linear(x: Array, w: Array, k: int, *,
                   impl: str = "popcount") -> Array:
    """M4: MHA output projection -> integer output for LayerNorm."""
    return rbmm_int(x, w, k, scheme="xnor", impl=impl)


def mode_f1_ffn1(x: Array, w1: Array, k: int, theta_relu: Array, *,
                 impl: str = "popcount") -> Tuple[Array, Array]:
    """F1: FFN layer I with fused ReLU+unsigned binarization; DC FULL out."""
    return rbmm_binary(x, w1, k, theta_relu, scheme="xnor", impl=impl,
                       return_dc=True)


def mode_f2_ffn2(h_bits: Array, w2: Array, ff: int, dc: Array, *,
                 acc: Optional[Array] = None,
                 impl: str = "popcount") -> Array:
    """F2: FFN layer II, {0,1} x {-1,1} -> integer, accumulated."""
    out = rbmm_int(h_bits, w2, ff, scheme="and_dc", dc=dc, impl=impl)
    if acc is not None:
        out = out + acc
    return out


# ---------------------------------------------------------------------------
# Dense-simulation twin (QAT forward; the oracle the packed path must match)
# ---------------------------------------------------------------------------


def rbmm_sim(a_vals: Array, b_vals: Array) -> Array:
    """Float matmul of already-binarized value matrices: a (..., M, K) in
    {-1,1} or {0,1}; b (..., P, K) in {-1,1}.  Integer-exact in f32."""
    out = jnp.einsum("...mk,...pk->...mp", a_vals.astype(jnp.float32),
                     b_vals.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)
