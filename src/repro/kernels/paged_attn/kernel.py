"""Fused paged gather-decode Pallas kernel (block tables resolved in-grid).

The PR 2 paged decode gathers every slot's pages into a contiguous ring
view (``k_pages[block_table]``) before the SPS attend ever runs — an extra
cache-sized HBM round-trip that exists only to linearize addressing.  The
binary-accelerator lineage this repo reproduces (COBRA's RBMM engine; BETA
and Ji et al.'s co-designed binarized accelerators) gets its efficiency
from never unpacking or re-materializing binary operands between pipeline
stages, and the same discipline applies to paging: the block table is an
*address* structure, so resolve it in the kernel's index map instead of in
data movement.

Grid: ``(B, Hkv, num_blocks)``, pages innermost.  The block table (plus
per-sequence lengths and the logical ring length) rides in as
scalar-prefetch operands — Mosaic reads ``block_table[b, j]`` while
scheduling the DMA for grid step ``(b, kh, j)``, so each kv head's K/V^T
page streams from HBM into VMEM exactly once and the gathered ring view
NEVER exists.  Per step the kernel

  1. scores the slot's one query token against the page's packed K rows
     (XNOR + popcount, the RBMM engine's M2 mode),
  2. polarizes with the per-(sequence, head) integer SPS threshold and
     masks by global ring index (``col <= pos`` and ``col < ring_len`` —
     unmapped table entries point at the trash page 0 and are always
     masked),
  3. packs the probability bits in-flight and consumes them against the
     page's packed V^T words (M3 mode, Eq. 7 ``and_dc``), accumulating
     the integer context across pages — tile sums telescope to
     ``2*popcount(probs & v^T) - nnz`` exactly as in the unfused path.

SPS has no softmax state, so page partials combine by plain int32
addition: the kernel is bitwise equal to ``SPSAttention._attend_cache``
over the gathered view (pinned by ``tests/test_paged_kernel.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import WORD


def _kernel(bt_ref, len_ref, ring_ref, qt_ref, kp_ref, vt_ref, th_ref,
            out_ref, *, d_h: int, page: int):
    # One (sequence, kv head, page) step on 2-D int32 tiles, ring columns
    # on sublanes and the kv head's G query heads on lanes: Mosaic lowers
    # neither unsigned reductions nor reshapes that split the head axis.
    b, j = pl.program_id(0), pl.program_id(2)
    qt = qt_ref[0, 0]                             # (dhp, G) query words
    k = kp_ref[0, 0]                              # (page, dhp)
    vt = vt_ref[0, 0]                             # (d_h, page/32)
    dhp = k.shape[1]
    # M2: XNOR + popcount scores, word by word -> (page, G)
    pc = sum(lax.population_count(~(k[:, w:w + 1] ^ qt[w:w + 1, :]))
             for w in range(dhp))
    pad = dhp * WORD - d_h
    c = 2 * pc - jnp.int32(d_h + 2 * pad)         # integer scores
    # SPS polarization + ring validity (trash-page cols are always masked)
    cols = j * page + lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    valid = (cols <= len_ref[b]) & (cols < ring_ref[0])
    probs = ((c >= th_ref[0, 0]) & valid).astype(jnp.int32)
    nnz = probs.sum(0, keepdims=True)             # (1, G)
    # in-flight pack (bits are disjoint, so the int32 sum is the OR and
    # 1 << 31 lands as the sign bit) -> M3 and_dc against V^T words
    pows = jnp.int32(1) << lax.broadcasted_iota(jnp.int32, (WORD, 1), 0)
    y = 0
    for w in range(page // WORD):
        pw = (probs[w * WORD:(w + 1) * WORD] * pows).sum(0, keepdims=True)
        y = y + lax.population_count(vt[:, w:w + 1] & pw)   # (d_h, G)
    part = 2 * y - nnz

    @pl.when(j == 0)
    def _init():
        out_ref[0, 0] = part

    @pl.when(j > 0)
    def _acc():
        out_ref[0, 0] += part


@functools.partial(jax.jit, static_argnames=("d_h", "interpret"))
def paged_gather_decode(q_bits: jax.Array, k_pages: jax.Array,
                        vt_pages: jax.Array, block_table: jax.Array,
                        lengths: jax.Array, ring_len: jax.Array,
                        theta: jax.Array, *, d_h: int,
                        interpret: bool = True) -> jax.Array:
    """One decode token per sequence, attended over packed pages in place.

    q_bits: (B, H, ceil(d_h/32)) uint32 packed query head bits.
    k_pages: (P+1, Hkv, page_size, ceil(d_h/32)) uint32 (page 0 = trash).
    vt_pages: (P+1, Hkv, d_h, page_size/32) uint32.
    block_table: (B, num_blocks) int32 physical page ids (0 = unmapped).
    lengths: (B,) int32 tokens written; ring_len: ()/(1,) int32 logical
    ring; theta: (B, H) int32 per-sequence SPS thresholds (row-granular
    thresholds resolve to this shape outside).
    Returns (B, H, d_h) int32 integer context == probs @ V.
    """
    b, h, dhp = q_bits.shape
    npages, hkv, page, _ = k_pages.shape
    g = h // hkv
    nblk = block_table.shape[1]
    i32 = lambda x: lax.bitcast_convert_type(x, jnp.int32)
    bt = jnp.clip(block_table, 0, npages - 1).astype(jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32).reshape(b)
    ring = jnp.asarray(ring_len, jnp.int32).reshape(1)
    # heads of one kv group on the minor (lane) axis
    qt = i32(q_bits).reshape(b, hkv, g, dhp).swapaxes(2, 3)
    th = jnp.asarray(theta, jnp.int32).reshape(b, hkv, 1, g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # block_table, lengths, ring_len
        grid=(b, hkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, dhp, g),
                         lambda bb, kh, j, bt, ln, rg: (bb, kh, 0, 0)),
            pl.BlockSpec((1, 1, page, dhp),
                         lambda bb, kh, j, bt, ln, rg: (bt[bb, j], kh, 0, 0)),
            pl.BlockSpec((1, 1, d_h, page // WORD),
                         lambda bb, kh, j, bt, ln, rg: (bt[bb, j], kh, 0, 0)),
            pl.BlockSpec((1, 1, 1, g),
                         lambda bb, kh, j, bt, ln, rg: (bb, kh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d_h, g),
                               lambda bb, kh, j, bt, ln, rg: (bb, kh, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, d_h=d_h, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, d_h, g), jnp.int32),
        interpret=interpret,
    )(bt, lens, ring, qt, i32(k_pages), i32(vt_pages), th)
    return out.swapaxes(2, 3).reshape(b, h, d_h)
