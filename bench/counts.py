"""Operations and bytes of the served model, computed from its sizes.

These are the roofline and utilization denominators.  They read only the
``reference`` block of a configuration file (``bench/configs/*.json``),
the same sizes the plain reference runs at, and a cell's engine shape.

Conventions:
  * A 1-bit multiply-accumulate counts as 2 operations, like a bf16 one.
    The peak they are held to is the bf16 MXU peak: the program runs
    binary products with more than 16 rows on the MXU after unpacking
    to bf16, and no unit of the chip is faster.
  * Attention counts 4 * head_dim operations per (query, key, head): the
    score (2 * head_dim) and the context (2 * head_dim).
  * Bytes are what one step must read at least once: the packed 1-bit
    weights, their float32 per-channel scales, the float32 embedding
    (and untied head), and the packed K/V bits of every key position the
    step attends over.
"""
from __future__ import annotations

from typing import Dict


def _r(cfg: Dict) -> Dict:
    return cfg["reference"]


def vocab_padded(cfg: Dict) -> int:
    v = _r(cfg)["vocab_size"]
    return -(-v // 256) * 256


def binary_params_per_layer(cfg: Dict) -> int:
    """Weights of the binarized projections of one layer."""
    r = _r(cfg)
    d, hd, ff = r["d_model"], r["head_dim"], r["d_ff"]
    q, kv = r["num_heads"] * hd, r["num_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def binary_out_channels_per_layer(cfg: Dict) -> int:
    """Output channels of the binarized projections (one float32 scale
    each)."""
    r = _r(cfg)
    d, hd, ff = r["d_model"], r["head_dim"], r["d_ff"]
    return r["num_heads"] * hd + 2 * r["num_kv_heads"] * hd + d + 2 * ff + d


def dense_ops_per_position(cfg: Dict, layers: int = 0) -> int:
    """Operations of one position through ``layers`` layers (all of them
    by default), attention's score and context left out."""
    n = layers or _r(cfg)["num_layers"]
    return n * 2 * binary_params_per_layer(cfg)


def attn_ops_per_key(cfg: Dict, layers: int = 0) -> int:
    """Operations of one (query, key) pair through ``layers`` layers."""
    r = _r(cfg)
    return (layers or r["num_layers"]) * 4 * r["num_heads"] * r["head_dim"]


def head_ops_per_row(cfg: Dict) -> int:
    return 2 * _r(cfg)["d_model"] * vocab_padded(cfg)


def keys_seen(cfg: Dict, position: int) -> int:
    """Keys a query at ``position`` attends over (window-capped)."""
    w = _r(cfg)["window"]
    n = position + 1
    return min(n, w) if w else n


def useful_ops(cfg: Dict, position: int, sampled: bool) -> int:
    """Operations one token at ``position`` needs; ``sampled`` adds the
    output head (prompt tokens other than the last need none)."""
    return (dense_ops_per_position(cfg) +
            attn_ops_per_key(cfg) * keys_seen(cfg, position) +
            (head_ops_per_row(cfg) if sampled else 0))


def embed_bytes(cfg: Dict) -> int:
    r = _r(cfg)
    emb = vocab_padded(cfg) * r["d_model"] * 4
    return emb if r.get("tie_embeddings", True) else 2 * emb


def layer_bytes(cfg: Dict) -> int:
    return (binary_params_per_layer(cfg) // 8 +
            4 * binary_out_channels_per_layer(cfg))


def weight_bytes(cfg: Dict, layers: int = 0) -> int:
    return embed_bytes(cfg) + (layers or _r(cfg)["num_layers"]) * \
        layer_bytes(cfg)


def kv_bytes_per_key(cfg: Dict, layers: int = 0) -> int:
    """Packed K and V bits of one key position in ``layers`` layers."""
    r = _r(cfg)
    return (layers or r["num_layers"]) * r["num_kv_heads"] * 2 * \
        r["head_dim"] // 8


def _chunk(cfg, slots, width, ring, layers=0, head_rows=None):
    """``slots`` rows of ``width`` positions, each over ``ring`` cached
    key columns plus the chunk itself."""
    pos = slots * width
    rows = slots if head_rows is None else head_rows
    return {"ops": (pos * dense_ops_per_position(cfg, layers) +
                    pos * (ring + width) * attn_ops_per_key(cfg, layers) +
                    rows * head_ops_per_row(cfg)),
            "bytes": (weight_bytes(cfg, layers) +
                      slots * ring * kv_bytes_per_key(cfg, layers))}


def _add(*steps):
    return {k: sum(s[k] for s in steps) for k in ("ops", "bytes")}


def unified_step(cfg: Dict, slots: int, width: int, ring: int,
                 draft_layers: int = 0, draft_ring: int = 0) -> Dict:
    """One pooled chunk step as the program computes it: every slot runs
    ``width`` positions over the ``ring`` gathered cache columns, logits
    at one row per slot; with a speculative draft, the draft's layers
    ingest the same chunk over their own ``draft_ring``."""
    step = _chunk(cfg, slots, width, ring)
    if draft_layers:
        step = _add(step, _chunk(cfg, slots, width, draft_ring,
                                 draft_layers))
    return step


def decode_step(cfg: Dict, slots: int, ring: int) -> Dict:
    """One pooled decode step: one position per slot over ``ring``
    gathered cache columns."""
    return _chunk(cfg, slots, 1, ring - 1 if ring else 0)


def spec_step(cfg: Dict, slots: int, k: int, ring: int, draft_layers: int,
              draft_ring: int) -> Dict:
    """One pooled speculative step: k + 1 draft decode steps of
    ``draft_layers`` layers (logits every step), then the trunk verifies
    k + 1 positions per slot over ``ring`` columns, logits at all of
    them."""
    draft = _chunk(cfg, slots, 1, draft_ring, draft_layers)
    verify = _chunk(cfg, slots, k + 1, ring, head_rows=slots * (k + 1))
    return _add(*([draft] * (k + 1)), verify)


def step_floor_s(step: Dict, peaks: Dict) -> float:
    """Least time the chip could take: the larger of operations over the
    bf16 peak and bytes over HBM bandwidth."""
    return max(step["ops"] / peaks["bf16_flops"],
               step["bytes"] / peaks["hbm_bytes_per_s"])
