"""Pooled binary KV-cache management for the serving engine.

The cache tensors live in the model layers (repro.models.attention KVCache
rings / PagedKVCache page arenas, SSM states); every contiguous leaf is
batch-leading, so a *slot pool* is just those same pytrees with batch ==
num_slots plus bookkeeping.  This module provides the slot-level operations
the continuous-batching engine needs — allocate / free / reset, scatter
freshly-prefilled per-request caches into pool slots, page-arena alloc /
free / growth / prefix-sharing bookkeeping (``PageArena``: refcounted
pages, hash-consed prompt-prefix keys, copy-on-write) — and the
sizing/occupancy reports that surface the paper's deploy-memory story
(packed uint32 K/V^T caches are 16-32x smaller than bf16 caches, so one
edge device holds a much deeper slot pool; paging lets short requests
return that memory early and long requests grow past any fixed ring;
sharing collapses N copies of a common system prompt into one).
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Dict, Iterator, List, Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.models.attention import KVCache, PagedKVCache

Caches = List[Dict[str, Any]]

_paged_leaf = lambda x: isinstance(x, PagedKVCache)


# ---------------------------------------------------------------------------
# Sizing / reports
# ---------------------------------------------------------------------------


def cache_bytes(caches: Caches) -> int:
    """Total device bytes held by a cache pytree (pages, rings, block
    tables, recurrent states — every array leaf counts)."""
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(caches))


def bf16_equivalent_bytes(caches: Caches) -> int:
    """What the same cache would cost with bf16 K/V (the paper's 16-32x
    bandwidth argument, applied to decode state)."""
    total = 0
    for x in jax.tree.leaves(caches):
        if x.dtype == np.uint32 or str(x.dtype) == "uint32":
            # packed: 32 binary values per word -> bf16 would be 64 bytes
            total += int(np.prod(x.shape)) * 64
        else:
            total += int(np.prod(x.shape)) * 2
    return total


@dataclasses.dataclass
class EngineReport:
    """Typed serving report — the stable metric schema.

    Every metric the engine (or ``cache_report``) can produce is a named
    field; a field is ``None`` when its feature did not run (e.g. the
    spec group without speculative decode).  ``as_dict()`` returns the
    FULL schema with those ``None``s intact, so JSON consumers always
    see every key and never KeyError across configs.

    Dict compatibility: the report also answers the old untyped-dict
    face — ``report["key"]``, ``"key" in report``, ``.get`` / ``.keys``
    / ``.items`` — with ``None`` fields behaving as ABSENT keys, exactly
    like the conditionally-present keys of the pre-typed dict (so
    ``"spec_accept_rate" in report`` is still False when spec decode was
    off).  New code should read attributes.

    Field groups (see ``cache_report`` for the semantics):
      memory      total_bytes .. compression_vs_bf16 (always set)
      slots       slots_total .. slot_utilization
      pages       pages_total .. pages_freed_rollback, peak_page_bytes
      spec        spec_drafted .. spec_tokens_per_step, spec_steps
      engine      iterations .. engine_compiles, prefill_batches,
                  prefill_chunks, requests, preemptions
      work        filled by every ``serve()`` call: chunk_positions
                  (pool rows x chunk width, summed over the pooled chunk
                  steps), chunk_valid_positions (those that carried a
                  token: prefill tokens plus one per decode row),
                  prefill_positions (prompt positions the chunk steps
                  computed), prefill_positions_adopted (the part whose
                  page every arena had adopted from a sharer, so the
                  step recomputed K/V^T that was already cached),
                  queue_wait_s ({rid: seconds from ``arrival_s`` to first
                  admission}, on ``serve()``'s clock)
      traffic     elapsed_s, goodput_under_slo (SLO-meeting requests'
                  tokens per second), slo_attainment (fraction of
                  requests meeting their SLO; no-SLO requests count as
                  met), ttft_p50_s / ttft_p99_s, tenants (per-tenant
                  rollup: requests, tokens, slo_met, preemptions,
                  ttft_p50_s, ttft_p99_s)
    """
    # memory (always set)
    total_bytes: float = 0.0
    bytes_per_token: float = 0.0
    bf16_equivalent_bytes: float = 0.0
    compression_vs_bf16: float = 0.0
    # slot pool
    slots_total: Optional[float] = None
    slots_active: Optional[float] = None
    occupancy: Optional[float] = None
    mean_slot_len: Optional[float] = None
    max_slot_len: Optional[float] = None
    decode_steps: Optional[float] = None
    slot_utilization: Optional[float] = None
    # page arena
    pages_total: Optional[float] = None
    pages_used: Optional[float] = None
    pages_free: Optional[float] = None
    page_utilization: Optional[float] = None
    peak_page_utilization: Optional[float] = None
    page_fragmentation: Optional[float] = None
    pages_reserved: Optional[float] = None
    pages_shared: Optional[float] = None
    prefix_lookups: Optional[float] = None
    prefix_hits: Optional[float] = None
    prefix_hit_rate: Optional[float] = None
    cow_copies: Optional[float] = None
    pages_freed_retire: Optional[float] = None
    pages_freed_rollback: Optional[float] = None
    peak_page_bytes: Optional[float] = None
    # speculative decode
    spec_drafted: Optional[float] = None
    spec_accepted: Optional[float] = None
    spec_accept_rate: Optional[float] = None
    spec_tokens_per_step: Optional[float] = None
    spec_steps: Optional[float] = None
    # engine loop
    iterations: Optional[float] = None
    dispatches_per_iteration: Optional[float] = None
    unified_compiles: Optional[float] = None
    engine_compiles: Optional[float] = None
    prefill_batches: Optional[float] = None
    prefill_chunks: Optional[float] = None
    requests: Optional[float] = None
    preemptions: Optional[float] = None
    # work the pooled chunk steps did, and admission waits
    chunk_positions: Optional[float] = None
    chunk_valid_positions: Optional[float] = None
    prefill_positions: Optional[float] = None
    prefill_positions_adopted: Optional[float] = None
    queue_wait_s: Optional[Dict[int, float]] = None
    # traffic / SLO
    elapsed_s: Optional[float] = None
    goodput_under_slo: Optional[float] = None
    slo_attainment: Optional[float] = None
    ttft_p50_s: Optional[float] = None
    ttft_p99_s: Optional[float] = None
    tenants: Optional[Dict[str, Dict[str, Any]]] = None

    @classmethod
    def field_names(cls) -> Tuple[str, ...]:
        """The full stable schema, in declaration order."""
        return tuple(f.name for f in dataclasses.fields(cls))

    def as_dict(self) -> Dict[str, Any]:
        """Full schema with nulls: EVERY field, ``None`` where the
        feature was off — the JSON face (downstream guards and diffs
        never KeyError across configs)."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}

    # -- untyped-dict compatibility face (None == absent) -------------------

    def __getitem__(self, key: str) -> Any:
        if key not in type(self).field_names():
            raise KeyError(key)
        val = getattr(self, key)
        if val is None:
            raise KeyError(key)
        return val

    def __setitem__(self, key: str, val: Any) -> None:
        if key not in type(self).field_names():
            raise KeyError(key)
        setattr(self, key, val)

    def __contains__(self, key: object) -> bool:
        return (key in type(self).field_names() and
                getattr(self, key) is not None)

    def get(self, key: str, default: Any = None) -> Any:
        val = (getattr(self, key)
               if key in type(self).field_names() else None)
        return default if val is None else val

    def keys(self) -> List[str]:
        return [k for k in type(self).field_names()
                if getattr(self, k) is not None]

    def items(self) -> Iterator[Tuple[str, Any]]:
        return ((k, getattr(self, k)) for k in self.keys())

    def __iter__(self) -> Iterator[str]:
        return iter(self.keys())


def cache_report(caches: Caches, *, seq_len: int, batch: int,
                 slot_lengths: Optional[Sequence[int]] = None,
                 active: Optional[Sequence[bool]] = None,
                 busy_slot_steps: int = 0, decode_steps: int = 0,
                 arenas: Optional[Sequence["PageArena"]] = None,
                 spec_drafted: Optional[int] = None,
                 spec_accepted: int = 0, spec_slot_steps: int = 0,
                 iterations: Optional[int] = None, dispatches: int = 0,
                 compiles: Optional[Dict[str, int]] = None
                 ) -> EngineReport:
    """Memory + (optionally) per-slot occupancy/utilization stats.

    Args:
      caches: the pool cache pytree (list of per-layer dicts).
      seq_len / batch: nominal capacity used for the bytes-per-token rate.
      slot_lengths / active: (num_slots,) pool state at report time.
      busy_slot_steps / decode_steps: run-aggregate counters
        (utilization = busy slot-steps / (decode steps * pool size)).
      arenas: page arenas backing the pool (paged mode); adds
        occupancy/fragmentation stats aggregated over every arena.

    Returns an ``EngineReport`` (typed; also answers the old dict face):
      total_bytes, bytes_per_token, bf16_equivalent_bytes,
      compression_vs_bf16; with slot_lengths also slots_total,
      slots_active, occupancy, mean_slot_len, max_slot_len, decode_steps,
      slot_utilization; with arenas also pages_total, pages_used,
      pages_free, page_utilization, peak_page_utilization,
      page_fragmentation (share of allocated page tokens not backing a
      live token — internal fragmentation of each sequence's last partial
      page, sampled at peak arena occupancy), pages_reserved (the trash
      page each arena keeps at id 0 — bookkeeping, counted SEPARATELY:
      it is excluded from pages_total/pages_used/pages_shared so the
      share-rate stats stay honest), pages_shared (usable pages mapped
      by >1 slot right now), prefix_lookups / prefix_hits /
      prefix_hit_rate (admission prefix pages that consulted the
      hash-cons table and the fraction adopted instead of allocated),
      cow_copies (copy-on-write privatizations), and pages_freed_retire /
      pages_freed_rollback (page frees from retirement-or-preemption
      ``release`` vs speculative-rollback ``truncate`` — separated so a
      spec-decode run can't masquerade rollback churn as retirement).
      With spec_drafted (speculative decode ran) also spec_drafted,
      spec_accepted, spec_accept_rate (accepted drafts / drafted) and
      spec_tokens_per_step (mean committed tokens per active slot per
      verify step: 1 bonus/resample + the accepted drafts).
      With iterations (the unified engine ran) also iterations,
      dispatches_per_iteration (jit calls / engine iterations — the
      one-kernel-iteration contract pins this at exactly 1.0),
      unified_compiles (XLA traces of the pooled unified forward; stays
      O(log max_prompt) via power-of-two width buckets) and
      engine_compiles (every engine-step trace: unified + decode + spec).
    """
    total = cache_bytes(caches)
    per_tok = total / max(seq_len * batch, 1)
    bf16 = bf16_equivalent_bytes(caches)
    report = EngineReport(
        total_bytes=float(total),
        bytes_per_token=float(per_tok),
        bf16_equivalent_bytes=float(bf16),
        compression_vs_bf16=float(bf16) / max(total, 1))
    if slot_lengths is not None:
        lens = np.asarray(slot_lengths, np.int64)
        act = (np.asarray(active, bool) if active is not None
               else np.ones(len(lens), bool))
        report["slots_total"] = float(len(lens))
        report["slots_active"] = float(act.sum())
        report["occupancy"] = float(act.mean()) if len(lens) else 0.0
        report["mean_slot_len"] = (float(lens[act].mean())
                                   if act.any() else 0.0)
        report["max_slot_len"] = float(lens[act].max()) if act.any() else 0.0
        report["decode_steps"] = float(decode_steps)
        report["slot_utilization"] = (
            busy_slot_steps / max(decode_steps * len(slot_lengths), 1))
    if arenas is not None:
        arenas = list(arenas)
        tot = sum(a.num_pages for a in arenas)
        used = sum(a.used_pages for a in arenas)
        peak = sum(a.peak_pages for a in arenas)
        report["pages_total"] = float(tot)
        report["pages_used"] = float(used)
        report["pages_free"] = float(tot - used)
        report["page_utilization"] = used / max(tot, 1)
        report["peak_page_utilization"] = peak / max(tot, 1)
        # internal fragmentation (allocated page tokens not backing a live
        # token) sampled at each arena's peak occupancy — the end-of-run
        # value is trivially 0 once everything retires.  A current-state
        # figure is derivable from allocated_tokens/live_tokens if needed.
        peak_alloc = sum(a.peak_pages * a.page_size for a in arenas)
        report["page_fragmentation"] = (
            sum(a.peak_frag * a.peak_pages * a.page_size for a in arenas)
            / max(peak_alloc, 1))
        # the reserved trash page (id 0, one per arena) backs every
        # unmapped block-table entry; it is bookkeeping, not occupancy —
        # count it separately so it can never read as used or shared
        report["pages_reserved"] = float(len(arenas))
        report["pages_shared"] = float(sum(a.shared_pages for a in arenas))
        lookups = sum(a.prefix_lookups for a in arenas)
        hits = sum(a.share_hits for a in arenas)
        report["prefix_lookups"] = float(lookups)
        report["prefix_hits"] = float(hits)
        report["prefix_hit_rate"] = hits / max(lookups, 1)
        report["cow_copies"] = float(sum(a.cow_copies for a in arenas))
        report["pages_freed_retire"] = float(
            sum(a.retire_frees for a in arenas))
        report["pages_freed_rollback"] = float(
            sum(a.rollback_frees for a in arenas))
    if spec_drafted is not None:
        report["spec_drafted"] = float(spec_drafted)
        report["spec_accepted"] = float(spec_accepted)
        report["spec_accept_rate"] = spec_accepted / max(spec_drafted, 1)
        report["spec_tokens_per_step"] = (
            (spec_accepted + spec_slot_steps) / max(spec_slot_steps, 1))
    if iterations is not None:
        report["iterations"] = float(iterations)
        report["dispatches_per_iteration"] = dispatches / max(iterations, 1)
        compiles = compiles or {}
        report["unified_compiles"] = float(compiles.get("unified", 0))
        report["engine_compiles"] = float(sum(compiles.values()))
    return report


# ---------------------------------------------------------------------------
# Slot-level cache surgery (all jit-friendly scatters on pooled pytrees)
# ---------------------------------------------------------------------------


def _insert_paged(pg: PagedKVCache, ring: KVCache,
                  idx: jax.Array) -> PagedKVCache:
    """Scatter per-request contiguous rings into a paged pool's pages.

    The ring must be wrap-free for logical positions (the engine prefills
    with ring size >= the longest prompt in the wave), so ring slot s holds
    token s and maps to logical page ``s // page_size``, offset
    ``s % page_size`` — resolved to physical pages through the pool's
    block-table rows at ``idx`` (which the engine synced beforehand).
    Positions past a slot's allocated pages (or past ``ring_len``) route to
    the trash page 0; their ring contents are zeros/garbage that no valid
    mask ever reads.
    """
    n, hkv, w_r, _ = ring.k_bits.shape
    page = pg.k_pages.shape[2]
    nblk = pg.block_table.shape[1]
    bt = pg.block_table[idx]                                  # (n, nblk)
    s = jnp.arange(w_r)
    lp, off = s // page, s % page
    beyond = lp >= nblk
    phys = jnp.take(bt, jnp.where(beyond, 0, lp), axis=1)     # (n, w_r)
    phys = jnp.where(beyond[None, :], 0, phys)
    off2 = jnp.broadcast_to(off[None, :], phys.shape)
    kp = pg.k_pages.at[phys, :, off2].set(
        jnp.swapaxes(ring.k_bits, 1, 2).astype(jnp.uint32))
    # V^T words: ring word j covers slots 32j..32j+31; a 32-aligned run
    # never straddles a page because page_size % 32 == 0, so whole words
    # move -> page (32j // page), in-page word ((32j % page) // 32)
    wp = ring.vt_bits.shape[-1]
    j32 = jnp.arange(wp) * packing.WORD
    lpw = j32 // page
    wj = (j32 % page) // packing.WORD
    beyond_w = lpw >= nblk
    physw = jnp.take(bt, jnp.where(beyond_w, 0, lpw), axis=1)  # (n, wp)
    physw = jnp.where(beyond_w[None, :], 0, physw)
    wj2 = jnp.broadcast_to(wj[None, :], physw.shape)
    vp = pg.vt_pages.at[physw, :, :, wj2].set(
        jnp.moveaxis(ring.vt_bits, 3, 1).astype(jnp.uint32))
    return pg._replace(k_pages=kp, vt_pages=vp,
                       length=pg.length.at[idx].set(
                           ring.length.astype(jnp.int32)))


def insert_slots(pool: Caches, seq_caches: Caches,
                 slots: Sequence[int]) -> Caches:
    """Scatter per-request caches (leading batch n) into pool ``slots``.

    Args:
      pool: pooled cache pytree (batch == num_slots leaves, or
        ``PagedKVCache`` arenas).
      seq_caches: per-request caches from prefill, leading batch n ==
        len(slots).  Attention entries are contiguous ``KVCache`` rings in
        both modes — prefill always builds rings; paged pools absorb them
        through the block table.
      slots: pool rows to write.

    Every contiguous leaf is batch-leading by construction (KVCache rings,
    SSM states, per-sequence lengths), so one tree-wide ``.at[slots].set``
    writes the entire decode state of each admitted request into its slot;
    paged attention leaves instead scatter the rings page-by-page
    (``_insert_paged``).  Returns the updated pool pytree (same shapes).
    """
    idx = jnp.asarray(np.asarray(slots, np.int32))

    def merge(p, s):
        if isinstance(p, PagedKVCache):
            return _insert_paged(p, s, idx)
        return p.at[idx].set(s.astype(p.dtype))

    return jax.tree.map(merge, pool, seq_caches, is_leaf=_paged_leaf)


def extract_slots(pool: Caches, slots) -> Caches:
    """Gather the decode state of ``slots`` as a batch-n cache pytree —
    the read-side inverse of ``insert_slots`` (jit-friendly; ``slots``
    may be a traced index array).

    Contiguous leaves gather their batch rows; paged leaves keep the
    SHARED page arenas whole and gather only block-table/length rows, so
    a chunk prefill on the extracted view writes straight into the pool's
    pages.  Pair with ``writeback_slots`` to commit updated state."""
    idx = jnp.asarray(slots)

    def ex(p):
        if isinstance(p, PagedKVCache):
            return p._replace(block_table=p.block_table[idx],
                              length=p.length[idx])
        return p[idx]

    return jax.tree.map(ex, pool, is_leaf=_paged_leaf)


def writeback_slots(pool: Caches, sub: Caches, slots) -> Caches:
    """Commit an ``extract_slots`` view back into the pool.

    Contiguous leaves scatter their rows; paged leaves adopt the view's
    page arrays wholesale (the view's pages ARE the pool's pages,
    functionally updated) and scatter only the per-slot lengths — block
    tables stay pool-owned (the host-side ``PageArena`` is authoritative
    and re-syncs them)."""
    idx = jnp.asarray(slots)

    def wb(p, s):
        if isinstance(p, PagedKVCache):
            return p._replace(k_pages=s.k_pages, vt_pages=s.vt_pages,
                              length=p.length.at[idx].set(s.length))
        return p.at[idx].set(s.astype(p.dtype))

    return jax.tree.map(wb, pool, sub, is_leaf=_paged_leaf)


def reset_slots(pool: Caches, slots: Sequence[int]) -> Caches:
    """Zero the given slots' decode state.

    Contiguous leaves (rings, lengths, SSM states) zero their batch rows;
    paged leaves zero the block-table rows (unmapping the pages — physical
    page contents are left stale, the next owner overwrites before any
    valid mask can read them) and lengths.  Returns the updated pool."""
    idx = jnp.asarray(np.asarray(slots, np.int32))

    def reset(p):
        if isinstance(p, PagedKVCache):
            return p._replace(
                block_table=p.block_table.at[idx].set(0),
                length=p.length.at[idx].set(0))
        return p.at[idx].set(jnp.zeros((), p.dtype))

    return jax.tree.map(reset, pool, is_leaf=_paged_leaf)


def slot_lengths(caches: Caches) -> np.ndarray:
    """Per-slot token counts, read from the first attention KVCache found
    (all layers agree — decode advances them in lockstep).  Works for both
    contiguous and paged attention caches (both carry ``.length``)."""
    for layer in caches:
        if isinstance(layer, dict) and "attn" in layer:
            return np.asarray(layer["attn"].length)
    # SSM-only stacks carry no position; report zeros of pool size
    leaves = jax.tree.leaves(caches)
    b = leaves[0].shape[0] if leaves else 0
    return np.zeros((b,), np.int32)


# ---------------------------------------------------------------------------
# Page-arena bookkeeping (host side)
# ---------------------------------------------------------------------------


class PageArena:
    """Refcounted free-list bookkeeping for one ring group's page arena.

    Layers that share a logical ring length (e.g. every full-attention
    layer, or every window-W layer) allocate in lockstep, so ONE arena's
    block tables mirror into each of the group's per-layer
    ``PagedKVCache.block_table`` arrays.  Physical page ids are 1..
    ``num_pages``; id 0 is the trash page every layer reserves — it is
    pure bookkeeping, never refcounted, and reported separately from the
    usable-page stats (``pages_reserved`` in ``cache_report``).

    Prefix sharing: pages carry refcounts and a hash-cons table from
    *page keys* (chain hashes over the bit-packed page content — in
    practice the token prefix that deterministically produces those K/V^T
    words) to physical pages.  ``set_prefix_keys`` records a slot's
    admission-time keys; ``grow`` then adopts an existing page (refcount
    +1) instead of allocating whenever a key already maps one, and
    registers freshly allocated prefix pages for future sharers.  A write
    that would diverge a shared page must go through ``cow`` first
    (copy-on-write: the writer gets a private page, other readers keep
    the original); a divergent write by a sole owner instead
    ``invalidate_key``s the page so no future sharer adopts stale
    content.  ``release`` only frees a page when its LAST reader leaves.

    The jax-side page arrays are owned by the engine (they flow through the
    jit'd decode step with donation); this object only tracks which pages
    back which (slot, logical page) and when the device tables are stale
    (``dirty``).
    """

    def __init__(self, num_pages: int, page_size: int, num_slots: int,
                 num_blocks: int, ring_len: int):
        if num_pages < num_blocks:
            raise ValueError(
                f"arena of {num_pages} pages cannot back one full "
                f"sequence ({num_blocks} blocks) — admission would "
                f"deadlock")
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_blocks = num_blocks
        self.ring_len = ring_len
        self._free: List[int] = list(range(num_pages, 0, -1))  # pop() -> 1,2..
        self.block_tables = np.zeros((num_slots, num_blocks), np.int32)
        self._counts = np.zeros((num_slots,), np.int64)
        self._lengths = np.zeros((num_slots,), np.int64)
        # prefix sharing: per-page refcounts (index 0 = trash, always 0),
        # hash-cons table both ways, and per-slot admission-time promises
        self._ref = np.zeros((num_pages + 1,), np.int64)
        self._key_page: Dict[bytes, int] = {}
        self._page_key: Dict[int, bytes] = {}
        self._promises: Dict[int, List[bytes]] = {}
        # (slot, logical page) mapped by adoption rather than allocation
        self._adopted = np.zeros((num_slots, num_blocks), bool)
        self.share_hits = 0        # pages adopted instead of allocated
        self.prefix_lookups = 0    # prefix pages that tried the table
        self.cow_copies = 0        # copy-on-write privatizations
        # page-free provenance: retirement/preemption (``release``) vs
        # speculative rollback (``truncate``) — kept separate so arena
        # stats stay honest about WHY pages came back
        self.retire_frees = 0
        self.rollback_frees = 0
        self.peak_pages = 0
        self.peak_frag = 0.0       # internal fragmentation at peak occupancy
        self.dirty = True          # device tables not yet synced

    # -- capacity ----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def allocated_tokens(self) -> int:
        """Token capacity of every allocated page (page-granular)."""
        return self.used_pages * self.page_size

    @property
    def live_tokens(self) -> int:
        """Ring-capped live tokens actually backing allocated pages."""
        return int(np.minimum(self._lengths, self.ring_len).sum())

    @property
    def shared_pages(self) -> int:
        """Usable pages currently mapped by more than one slot.  The trash
        page 0 backs every unmapped table entry but is never refcounted,
        so it can never masquerade as a shared page here."""
        return int((self._ref > 1).sum())

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    def freeable_pages(self, slot: int) -> int:
        """Pages that would return to the free list if ``slot`` released
        right now — its sole-owner (refcount 1) pages.  Shared prefix
        pages stay with their other readers, so a slot riding a popular
        system prompt frees almost nothing when evicted; COW-aware
        preemption (``PolicyConfig.cow_victims``) uses this to prefer
        victims whose eviction actually relieves arena pressure."""
        n = int(self._counts[slot])
        return sum(1 for lp in range(n)
                   if self._ref[int(self.block_tables[slot, lp])] == 1)

    def page_key(self, page: int) -> Optional[bytes]:
        """The hash-cons key registered for ``page`` (None if none)."""
        return self._page_key.get(page)

    def blocks_for(self, length: int) -> int:
        """Logical pages needed to hold ``length`` tokens (ring-capped)."""
        return -(-min(length, self.ring_len) // self.page_size)

    def set_prefix_keys(self, slot: int, keys: Sequence[bytes],
                        prompt_len: int) -> None:
        """Record ``slot``'s admission-time prefix page keys.

        Only FULL pages of the prompt are shareable, and only when the
        whole prompt fits the logical ring (``prompt_len <= ring_len``) —
        a wrapped prefill ring holds later tokens at early ring slots, so
        its page content is no longer the pure token prefix the key
        promises.  ``grow`` consults these promises page by page: a key
        already in the table is adopted (refcount +1, no allocation); a
        fresh allocation under a promise registers the key for future
        sharers."""
        if prompt_len <= self.ring_len:
            n = min(len(keys), prompt_len // self.page_size)
            self._promises[slot] = list(keys[:n])
        else:
            self._promises[slot] = []

    def _prefix_hits(self, slot: int, have: int, need: int) -> int:
        keys = self._promises.get(slot, ())
        return sum(1 for lp in range(have, min(need, len(keys)))
                   if keys[lp] in self._key_page)

    def can_grow(self, slot: int, length: int) -> bool:
        need = self.blocks_for(length)
        have = int(self._counts[slot])
        return (need - have - self._prefix_hits(slot, have, need)
                <= len(self._free))

    # -- alloc / free ------------------------------------------------------

    def _note_peak(self) -> None:
        if self.used_pages >= self.peak_pages:
            self.peak_pages = self.used_pages
            self.peak_frag = 1 - (self.live_tokens /
                                  max(self.allocated_tokens, 1))

    def grow(self, slot: int, length: int) -> bool:
        """Ensure ``slot`` maps pages covering ``length`` tokens.

        New logical pages under an admission promise whose key is already
        hash-consed ADOPT the existing physical page (refcount +1) instead
        of allocating; fresh allocations under a promise register their
        key.  Returns False (mapping nothing) when the arena cannot
        satisfy the growth — the engine then preempts a victim and
        retries."""
        need = self.blocks_for(length)
        have = int(self._counts[slot])
        if not self.can_grow(slot, length):
            return False
        keys = self._promises.get(slot, ())
        for lp in range(have, need):
            key = keys[lp] if lp < len(keys) else None
            page = self._key_page.get(key) if key is not None else None
            if key is not None:
                self.prefix_lookups += 1
            self._adopted[slot, lp] = page is not None
            if page is not None:
                self._ref[page] += 1
                self.share_hits += 1
            else:
                page = self._free.pop()
                self._ref[page] = 1
                if key is not None:
                    self._key_page[key] = page
                    self._page_key[page] = key
            self.block_tables[slot, lp] = page
        self._lengths[slot] = max(int(self._lengths[slot]), length)
        if need > have:
            self._counts[slot] = need
            self.dirty = True
            self._note_peak()
        return True

    def release(self, slot: int) -> None:
        """Drop ``slot``'s reference on every page it maps and unmap its
        block-table row (retirement or preemption).  A page returns to
        the free list — and its hash-cons key retires — only when the
        LAST reader leaves."""
        n = int(self._counts[slot])
        for lp in range(n):
            page = int(self.block_tables[slot, lp])
            self._ref[page] -= 1
            if self._ref[page] == 0:
                self._free.append(page)
                self.retire_frees += 1
                self.invalidate_key(page)
        if n:
            self.block_tables[slot, :n] = 0
            self.dirty = True
        self._adopted[slot] = False
        self._counts[slot] = 0
        self._lengths[slot] = 0
        self._promises.pop(slot, None)

    def truncate(self, slot: int, length: int) -> int:
        """Un-grow ``slot`` to exactly the pages covering ``length``
        tokens — the speculative-rollback face of ``grow``.  Pages past
        ``blocks_for(length)`` drop this slot's reference and return to
        the free list with the LAST reader, exactly like ``release``,
        but the frees are counted separately (``rollback_frees``) so
        arena stats never conflate rejected-draft rollback with
        retirement.  Returns the number of pages freed to the list."""
        need = self.blocks_for(length)
        have = int(self._counts[slot])
        freed = 0
        for lp in range(need, have):
            page = int(self.block_tables[slot, lp])
            self._ref[page] -= 1
            if self._ref[page] == 0:
                self._free.append(page)
                self.rollback_frees += 1
                freed += 1
                self.invalidate_key(page)
            self.block_tables[slot, lp] = 0
            self._adopted[slot, lp] = False
        if have > need:
            self._counts[slot] = need
            self.dirty = True
        self._lengths[slot] = min(int(self._lengths[slot]), length)
        return freed

    def adopted_mask(self, slot: int, start: int, end: int) -> np.ndarray:
        """(end - start,) bool over ``slot``'s token positions
        [start, end): True where the position's logical page was adopted
        from a sharer by ``grow`` (its K/V^T already sits in the page), not
        allocated or privatized by ``cow``."""
        pos = np.arange(start, end)
        return self._adopted[slot, (pos % self.ring_len) // self.page_size]

    # -- copy-on-write -----------------------------------------------------

    def write_page(self, slot: int, pos: int) -> Tuple[int, int]:
        """(logical page, physical page) the decode write at token
        position ``pos`` will land in (ring arithmetic included)."""
        lp = (pos % self.ring_len) // self.page_size
        return lp, int(self.block_tables[slot, lp])

    def can_cow(self) -> bool:
        return bool(self._free)

    def cow(self, slot: int, lp: int) -> Tuple[int, int]:
        """Privatize ``slot``'s logical page ``lp`` before a divergent
        write: allocate a fresh page, move the slot's reference onto it
        and return ``(old, new)`` physical ids so the engine can copy the
        page payload on device.  Other readers keep the original page —
        COW is never visible to them.  Caller checks ``can_cow`` first
        (exhaustion preempts, exactly like ``grow``)."""
        old = int(self.block_tables[slot, lp])
        new = self._free.pop()
        self._ref[old] -= 1
        self._ref[new] = 1
        self.block_tables[slot, lp] = new
        self._adopted[slot, lp] = False
        self.cow_copies += 1
        self.dirty = True
        self._note_peak()
        return old, new

    def invalidate_key(self, page: int) -> None:
        """Retire ``page``'s hash-cons key (sole-owner divergent write, or
        last-reader release): future admissions must not adopt content
        that no longer matches the key's promise."""
        key = self._page_key.pop(page, None)
        if key is not None:
            self._key_page.pop(key, None)


class SlotPool:
    """Free-list bookkeeping over a pooled cache batch.

    The jax-side cache pytrees are owned by the engine (they flow through
    the jit'd decode step with donation); this object tracks which batch
    rows are live, which request occupies each, and utilization counters
    for the serving report."""

    def __init__(self, num_slots: int):
        if num_slots <= 0:
            raise ValueError("num_slots must be positive")
        self.num_slots = num_slots
        self._free: List[int] = list(range(num_slots))[::-1]  # pop() -> 0,1,..
        self._owner: Dict[int, Any] = {}
        self.busy_slot_steps = 0
        self.decode_steps = 0

    # -- alloc / free -------------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return len(self._owner)

    @property
    def active_slots(self) -> List[int]:
        return sorted(self._owner)

    def owner(self, slot: int):
        return self._owner.get(slot)

    def alloc(self, rid) -> int:
        """Claim a free slot for request ``rid``; raises when full."""
        if not self._free:
            raise RuntimeError("slot pool exhausted")
        slot = self._free.pop()
        self._owner[slot] = rid
        return slot

    def release(self, slot: int):
        """Retire the request in ``slot``; the slot is immediately
        reusable (returns the owning rid)."""
        rid = self._owner.pop(slot)
        self._free.append(slot)
        return rid

    # -- stats --------------------------------------------------------------

    def tick(self, busy: Optional[int] = None) -> None:
        """Record one pooled decode step for utilization accounting.
        ``busy`` overrides the busy-slot count (the engine passes the
        number of DECODING slots so mid-prefill slots don't inflate
        utilization); defaults to every allocated slot."""
        self.decode_steps += 1
        self.busy_slot_steps += self.active_count if busy is None else busy

    @property
    def utilization(self) -> float:
        return self.busy_slot_steps / max(
            self.decode_steps * self.num_slots, 1)
