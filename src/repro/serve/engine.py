"""Continuous-batching serve engine on a pooled binary KV cache.

Two scheduling modes over the same jit'd decode step (donated caches, the
packed uint32 K/V^T caches update in place):

  static      ``generate(prompts_2d)`` — one equal-length batch prefills
              once, then decode steps run lockstep to a fixed horizon.
  continuous  ``generate([variable-length prompts])`` / ``serve(requests)``
              — a priority/FIFO scheduler admits requests into a fixed
              pool of cache slots.  Admission is host-side bookkeeping
              only: every request (all five model families) becomes an
              in-flight prefill row, and ONE pooled forward per engine
              iteration advances every in-flight stream at once.  Slots
              retire on EOS or token budget and are backfilled from the
              waiting queue on the next iteration.

The unified iteration is the engine's core invariant: each pass of the
serve loop issues exactly ONE jit dispatch.  Iterations with any
in-flight prefill run the pooled chunk-continuation forward
(``LM.prefill_with_cache`` with ``caches=``) over the WHOLE slot pool —
prefill rows carry their next chunk (``valid = n`` real tokens), decode
rows ride as width-1 chunks (their pending token, ``valid = 1``), and
empty slots are inactive rows (``valid = 0``: no cache write, frozen
recurrent carries).  The per-row ``(start, valid, fresh)`` vectors are
the mode mask: ``start`` is the cached prefix length, ``valid`` the live
chunk width, and ``fresh`` (start == 0 with valid > 0) resets recurrent
carries to their init values inside the same jit.  Decode rows are
bit-identical to the dedicated decode step (integer-exact binary
attention makes chunk partial sums associative; decode == width-1 chunk),
so WHICH iterations are mixed never changes tokens.  Pure-decode
iterations keep the dedicated pooled decode (or speculative verify) step
— still one dispatch.  Dispatches per iteration are therefore 1 instead
of O(in-flight prefills), and compile count stays O(log max_prompt):
chunked configs trace one fixed width, unchunked configs trace
power-of-two width buckets.

With ``ServeConfig.paged`` the per-slot full-length rings are replaced by
a shared page arena + per-slot block tables (repro.models.attention
PagedKVCache): short requests return pages the moment they retire, long
requests grow past the old ``max_len`` ring cap (up to ``max_blocks *
page_size``), and when the arena is exhausted the engine *preempts* the
lowest-priority slot back to the scheduler queue (recompute-on-resume)
instead of deadlocking.  Block-table gathers resolve each slot's pages
inside the pooled step (or the fused repro.kernels.paged_attn kernel
does, with ``BinaryConfig.paged_kernel``).

``ServeConfig.prefix_share`` (default on, paged mode) adds prefix sharing
on top: admission hash-conses every full prompt page (chain digests over
the token prefix that deterministically produces the page's packed K/V^T
words), so requests opening with the same system prompt ADOPT one shared,
refcounted copy of those pages instead of allocating their own.  Decode
writes that would diverge a shared page copy-on-write behind the other
readers' backs (the pre-step sweep); prefill-chunk writes need no COW —
a chunk rewrites exactly the bits its page key promises (equal keys =>
bitwise-equal content), so sharers and the writer see identical pages
either way.  Sole-owner divergent writes retire the hash key, and pages
free only when their last reader leaves — output stays token-for-token
identical to the unshared paths while peak mapped pages drop by the
shared-prefix footprint per extra sharer.

With ``ServeConfig.prefill_chunk`` prompts longer than the chunk stream
through the unified step one fixed-size chunk per iteration — decode
slots keep emitting tokens in the SAME pooled forward while a long
prompt loads, so time-to-first-token stays bounded for the short
requests sharing the pool.  All five families chunk: attention resumes
through the ring/block-table prefix attend, recurrent families resume
through their carry state (``ssm.py`` ``state=``), both bit-identical to
whole-prompt prefill at any chunk size.  In-flight prefills are
preemption-safe (eviction mid-prefill requeues the request; resume
recomputes from the prompt) and grow their pages chunk by chunk in paged
mode.

``ServeConfig.spec_decode`` layers self-speculative decoding on the
pure-decode iterations: a layer-truncated draft sharing the trunk's
packed weights (or an independent small draft passed to the engine)
proposes k tokens per slot per iteration, and ONE pooled verify forward
— the chunk-prefill prefix attend over the ring/block-table caches —
scores all k+1 positions at once.  The verify never writes the caches;
acceptance (greedy exact-match, or rejection sampling for
temperature/top_k so the output distribution is provably unchanged)
picks each slot's accepted prefix and exactly that prefix commits, so
rejected drafts roll back bit-exactly in every layout — wrapped SWA
rings and shared pages (conservatively COW'd before the step) included —
and over-grown pages un-grow back to the arena (``PageArena.truncate``,
counted apart from retirement frees).  On mixed iterations decode slots
advance one plain token through the unified forward while the draft
caches ingest the same token in the same jit, so the draft state stays
in lockstep without a second dispatch.  Decode is bandwidth-bound on the
binary datapath, so verifying k+1 tokens costs about one decode step of
weight/cache traffic: accepted tokens amortize the pool's per-step
memory traffic.

The binary cache is what makes deep pools cheap: each slot's decode state
is 16-32x smaller than a bf16 KV cache (the paper's edge bandwidth story,
transferred to serving), so slot count — i.e. serving concurrency — scales
by the same factor at fixed memory.  ``cache_report`` surfaces the memory
win, slot occupancy/utilization, page-arena occupancy/fragmentation,
speculative accept rate and the dispatch/compile discipline
(``dispatches_per_iteration``, ``unified_compiles``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.models.attention import KVCache, PagedKVCache, PageSpec
from repro.serve import kvcache, sampler as sampler_lib
from repro.serve.policy import (PolicyConfig, Scheduler, SchedulingPolicy,
                                _pow2_bucket, make_policy)

__all__ = ["CacheConfig", "SpecConfig", "PolicyConfig", "ServeConfig",
           "SLO", "Request", "Scheduler", "SchedulingPolicy",
           "ServeEngine"]

Params = Any


@dataclasses.dataclass
class CacheConfig:
    """KV-cache layout knobs (``ServeConfig.cache``).

    Attributes:
      max_len: contiguous decode ring size (>= prompt + new tokens for
        full-attention stacks; windowed stacks ring at their window).  In
        paged mode the full-attention cap is ``max_blocks * page_size``
        instead.
      paged: replace per-slot rings with a page arena + block tables.
      page_size: tokens per page; must be a positive multiple of 32 (the
        uint32 packing word) so V^T bit-packing never straddles pages.
      max_blocks: per-slot block-table width for full-attention layers;
        defaults to ceil(max_len / page_size).  Capacity is
        ``max_blocks * page_size`` and may exceed ``max_len``.
      num_pages: usable pages in the shared full-capacity arena; defaults
        to ``num_slots * max_blocks`` (fully provisioned — no preemption).
        Sizing it below that is safe: exhaustion preempts, never deadlocks.
      prefix_share: paged mode only — admission hash-conses full prompt
        pages (chain hashes over the token prefix, which deterministically
        produces the page's bit-packed K/V^T words) so requests with a
        shared prompt prefix map the SAME physical pages (refcounted).
        Divergent writes copy-on-write behind the other readers' backs,
        so output stays token-for-token identical to the unshared paths.
        False keeps the PR 2 one-owner-per-page behavior (the escape
        hatch the benchmark compares against).
    """
    max_len: int = 2048
    paged: bool = False
    page_size: int = 32
    max_blocks: Optional[int] = None
    num_pages: Optional[int] = None
    prefix_share: bool = True

    def page_spec(self) -> PageSpec:
        """Resolve the paged-cache sizing (PageSpec validates itself)."""
        if self.max_blocks is not None:
            blocks = self.max_blocks
        else:
            blocks = (-(-self.max_len // self.page_size)
                      if self.page_size > 0 else 1)
        return PageSpec(page_size=self.page_size, max_blocks=blocks,
                        num_pages=self.num_pages or 0)


@dataclasses.dataclass
class SpecConfig:
    """Speculative-decode knobs (``ServeConfig.spec``).

    Attributes:
      k: self-speculative decoding — k drafted tokens per slot per
        pure-decode iteration, batch-verified in ONE pooled k+1-token
        verify forward that reuses the chunk-prefill prefix attend.
        Accepted prefixes commit to the caches; rejected tails are never
        written (rollback is exact in every layout, wrapped SWA rings
        included) and in paged mode over-grown pages un-grow back to the
        arena.  Greedy output is bit-identical to plain decode;
        temperature/top_k use rejection-sampling acceptance so the token
        distribution is provably unchanged.  None disables.
        Attention-only stacks (recurrent families decode
        non-speculatively).
      draft_layers: depth of the layer-truncated draft sharing the
        trunk's packed weights (clamped to the stack depth; a full-depth
        "draft" degenerates to the trunk itself and accepts everything).
        Ignored when an explicit draft model is passed to ``ServeEngine``
        — an independent small binary draft with its own params.
    """
    k: Optional[int] = None
    draft_layers: int = 1

    def __post_init__(self):
        if self.k is not None and self.k < 1:
            raise ValueError(f"spec_decode must draft at least one token "
                             f"per step, got {self.k}")
        if self.k is not None and self.draft_layers < 1:
            raise ValueError(f"spec_draft_layers must be >= 1, got "
                             f"{self.draft_layers}")


_FLAT_CACHE = ("max_len", "paged", "page_size", "max_blocks", "num_pages",
               "prefix_share")
_FLAT_SPEC = {"spec_decode": "k", "spec_draft_layers": "draft_layers"}
_FLAT_POLICY = ("prefill_chunk",)


class ServeConfig:
    """Engine-level serving knobs, grouped into sub-configs.

    Top-level fields are the sampling/pool knobs every run touches:
      sampler / temperature / top_k / seed: token sampling policy
        (sampler is one of greedy | temperature | top_k).
      num_slots: continuous-batching pool size (concurrent sequences).
      eos_id: default retirement token (per-request ``Request.eos_id``
        overrides).

    The rest group by subsystem:
      cache: ``CacheConfig`` — ring/page layout, capacity, prefix
        sharing.
      spec: ``SpecConfig`` — speculative batch-verify decode.
      policy: ``repro.serve.policy.PolicyConfig`` — scheduling policy,
        chunked prefill width, SLO-adaptive chunking, tenant quotas,
        COW-aware preemption.

    Compatibility: the pre-regroup flat keywords (``max_len=``,
    ``paged=``, ``prefill_chunk=``, ``spec_decode=``, ...) still
    construct — they map onto the sub-configs and emit a single
    ``DeprecationWarning`` — and read-through properties
    (``cfg.max_len``, ``cfg.prefill_chunk``, ``cfg.spec_decode``, ...)
    keep every old call site working unchanged.
    """

    def __init__(self, *, sampler: str = "greedy",
                 temperature: float = 1.0, top_k: int = 40, seed: int = 0,
                 num_slots: int = 4, eos_id: Optional[int] = None,
                 cache: Optional[CacheConfig] = None,
                 spec: Optional[SpecConfig] = None,
                 policy: Optional[PolicyConfig] = None, **flat):
        self.sampler = sampler
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self.num_slots = num_slots
        self.eos_id = eos_id
        cache = cache if cache is not None else CacheConfig()
        spec = spec if spec is not None else SpecConfig()
        policy = policy if policy is not None else PolicyConfig()
        if flat:
            unknown = [k for k in flat if k not in _FLAT_CACHE and
                       k not in _FLAT_SPEC and k not in _FLAT_POLICY]
            if unknown:
                raise TypeError(f"ServeConfig got unexpected keyword "
                                f"arguments {sorted(unknown)}")
            warnings.warn(
                f"flat ServeConfig keywords {sorted(flat)} are "
                f"deprecated: pass cache=CacheConfig(...), "
                f"spec=SpecConfig(...) and/or policy=PolicyConfig(...)",
                DeprecationWarning, stacklevel=2)
            ck = {k: v for k, v in flat.items() if k in _FLAT_CACHE}
            if ck:
                cache = dataclasses.replace(cache, **ck)
            sk = {_FLAT_SPEC[k]: v for k, v in flat.items()
                  if k in _FLAT_SPEC}
            if sk:
                spec = dataclasses.replace(spec, **sk)
            pk = {k: v for k, v in flat.items() if k in _FLAT_POLICY}
            if pk:
                policy = dataclasses.replace(policy, **pk)
        self.cache = cache
        self.spec = spec
        self.policy = policy

    def __repr__(self) -> str:
        return (f"ServeConfig(sampler={self.sampler!r}, "
                f"temperature={self.temperature}, top_k={self.top_k}, "
                f"seed={self.seed}, num_slots={self.num_slots}, "
                f"eos_id={self.eos_id}, cache={self.cache}, "
                f"spec={self.spec}, policy={self.policy})")

    def __eq__(self, other) -> bool:
        if not isinstance(other, ServeConfig):
            return NotImplemented
        return ((self.sampler, self.temperature, self.top_k, self.seed,
                 self.num_slots, self.eos_id, self.cache, self.spec,
                 self.policy) ==
                (other.sampler, other.temperature, other.top_k,
                 other.seed, other.num_slots, other.eos_id, other.cache,
                 other.spec, other.policy))

    def page_spec(self) -> PageSpec:
        """Resolve the paged-cache sizing (PageSpec validates itself)."""
        return self.cache.page_spec()

    # -- flat read-through face (pre-regroup call sites) -------------------

    @property
    def max_len(self) -> int:
        return self.cache.max_len

    @property
    def paged(self) -> bool:
        return self.cache.paged

    @property
    def page_size(self) -> int:
        return self.cache.page_size

    @property
    def max_blocks(self) -> Optional[int]:
        return self.cache.max_blocks

    @property
    def num_pages(self) -> Optional[int]:
        return self.cache.num_pages

    @property
    def prefix_share(self) -> bool:
        return self.cache.prefix_share

    @property
    def prefill_chunk(self) -> Optional[int]:
        return self.policy.prefill_chunk

    @property
    def spec_decode(self) -> Optional[int]:
        return self.spec.k

    @property
    def spec_draft_layers(self) -> int:
        return self.spec.draft_layers


@dataclasses.dataclass
class SLO:
    """Per-request latency targets (None = unconstrained).

    A finished request *meets* its SLO when its time-to-first-token and
    mean time-per-output-token both land within budget; the engine's
    ``goodput_under_slo`` counts only SLO-meeting requests' tokens, so
    scheduling that starves someone shows up as lost goodput even when
    raw throughput looks fine.

    Attributes:
      ttft_s: time-to-first-token budget, seconds from ``arrival_s``.
      tpot_s: mean seconds per output token after the first.
    """
    ttft_s: Optional[float] = None
    tpot_s: Optional[float] = None

    def met(self, ttft_s: Optional[float], tpot_s: float) -> bool:
        """Did a request with these measurements make its targets?"""
        if self.ttft_s is not None and (ttft_s is None or
                                        ttft_s > self.ttft_s):
            return False
        if self.tpot_s is not None and tpot_s > self.tpot_s:
            return False
        return True


@dataclasses.dataclass
class Request:
    """One decode request for the continuous engine.

    Attributes:
      rid: caller-chosen id; results key on it.
      tokens: (S,) int32 prompt (S >= 1).
      max_new_tokens: total generation budget (> 0); survives preemption —
        tokens generated before a preemption still count against it.
      eos_id: retirement token; falls back to ``ServeConfig.eos_id``.
      priority: higher runs first; the LOWEST-priority slot (ties: most
        recently admitted) is preempted when the page arena is exhausted
        (the scheduling policy can refine the tie-break).
      tenant: traffic-class label for quota fair-share and the per-tenant
        report rollups; the default lumps everything into one class.
      arrival_s: open-loop arrival offset, seconds from serve() start —
        the request is invisible to admission until the engine clock
        reaches it.  0.0 (the default) reproduces the closed-loop
        everything-queued-upfront behavior exactly.
      slo: latency targets for the goodput accounting (None = always
        counts as met).
    """
    rid: int
    tokens: np.ndarray               # (S,) int32 prompt
    max_new_tokens: int
    eos_id: Optional[int] = None     # falls back to ServeConfig.eos_id
    priority: int = 0
    tenant: str = "default"
    arrival_s: float = 0.0
    slo: Optional[SLO] = None


class _SlotState:
    """Python-side generation state for one occupied slot."""

    __slots__ = ("request", "generated", "eos_id", "cache_len", "admit_seq")

    def __init__(self, request: Request, eos_id: Optional[int],
                 prompt_len: int, admit_seq: int,
                 resumed: Sequence[int] = ()):
        self.request = request
        self.generated: List[int] = list(resumed)
        self.eos_id = request.eos_id if request.eos_id is not None else eos_id
        self.cache_len = prompt_len       # tokens written to the cache
        self.admit_seq = admit_seq

    def push(self, token: int) -> bool:
        """Record a token; True when the request should retire."""
        self.generated.append(token)
        if self.eos_id is not None and token == self.eos_id:
            return True
        return len(self.generated) >= self.request.max_new_tokens


class _PrefillState:
    """An in-flight prefill occupying a pool slot.

    EVERY admitted request passes through this state — short prompts for
    one unified iteration, chunked long prompts for several.  ``toks`` is
    prompt + pre-preemption tokens (``pre``); ``done`` counts tokens
    already written to the slot's caches.  The slot joins the decode rows
    once every chunk has landed (its first token is sampled by the same
    unified forward that lands the last chunk)."""

    __slots__ = ("request", "toks", "pre", "done", "admit_seq")

    def __init__(self, request: Request, toks: np.ndarray,
                 pre: Sequence[int], admit_seq: int):
        self.request = request
        self.toks = toks
        self.pre: List[int] = list(pre)
        self.done = 0
        self.admit_seq = admit_seq


class ServeEngine:
    def __init__(self, model, dparams: Params, cfg: ServeConfig,
                 draft_model=None, draft_dparams: Optional[Params] = None,
                 policy: Optional[SchedulingPolicy] = None):
        """``draft_model``/``draft_dparams`` optionally supply an
        INDEPENDENT speculative draft (a small BinaryConfig model with
        its own converted params); with ``cfg.spec.k`` set and no
        explicit draft, a layer-truncated draft sharing the trunk's
        packed weights is built lazily (``cfg.spec.draft_layers``).
        ``policy`` optionally injects a custom ``SchedulingPolicy``
        instance; by default each ``serve()`` call builds a fresh one
        from ``cfg.policy`` (an injected instance is reused across
        calls, so its fairness accounts carry over)."""
        self.model = model
        self.dparams = dparams
        self.cfg = cfg
        self._policy_proto = policy
        if (draft_model is None) != (draft_dparams is None):
            raise ValueError("pass draft_model and draft_dparams together")
        self.draft_model = draft_model
        self.draft_dparams = draft_dparams
        self._decode_jit = None
        self._unified_jit = None
        self._spec_jit = None
        # trace-count probe: each counter increments INSIDE the traced
        # function body, i.e. once per XLA compilation (shape bucket),
        # never per dispatch — the dispatch-count regression test pins
        # both axes of the one-kernel-iteration contract through these
        self._compiles = {"unified": 0, "decode": 0, "spec": 0}
        self._sample = {
            "greedy": lambda lg, k: sampler_lib.greedy(lg),
            "temperature": lambda lg, k: sampler_lib.temperature(
                lg, k, cfg.temperature),
            "top_k": lambda lg, k: sampler_lib.top_k(
                lg, k, cfg.top_k, cfg.temperature),
        }[cfg.sampler]

    # -- decode step --------------------------------------------------------

    def _build_decode(self):
        def step(dparams, token, caches, key):
            self._compiles["decode"] += 1
            logits, caches = self.model.decode_step(dparams, token, caches)
            key, sub = jax.random.split(key)
            nxt = self._sample(logits[:, -1:], sub)
            return nxt, caches, key

        self._decode_jit = jax.jit(step, donate_argnums=(2,))

    # -- unified iteration ----------------------------------------------------

    def _build_unified(self, with_draft: bool):
        """ONE pooled forward that advances every in-flight stream.

        The whole slot pool rides the cache-continuation prefill — the
        per-row ``(start, valid, fresh)`` vectors are the mode mask:

          prefill chunk   start = tokens done,  valid = chunk width
          decode          start = cache length, valid = 1 (pending token)
          inactive        valid = 0 (no write, frozen recurrent carries)

        ``fresh`` rows (start == 0, valid > 0) reset their recurrent
        carries to init values inside the same jit, so admission costs
        no extra dispatch.  Logits come back at each row's last real
        position, so the same sample serves decode rows AND the first
        token of a prefill row landing its final chunk.  With a
        speculative draft, the draft pool ingests the identical chunk in
        the same trace so its cache stays in lockstep with the trunk —
        still one dispatch."""

        def trunk(dparams, toks, caches, start, valid, fresh, key):
            caches = self.model.reset_recurrent_rows(caches, fresh)
            logits, caches = self.model.prefill_with_cache(
                dparams, toks, caches=caches, start=start, seq_lens=valid)
            key, sub = jax.random.split(key)
            nxt = self._sample(logits, sub)
            return nxt, caches, key

        if with_draft:
            def step(dparams, ddparams, toks, caches, dcaches, start,
                     valid, fresh, key):
                self._compiles["unified"] += 1
                nxt, caches, key = trunk(dparams, toks, caches, start,
                                         valid, fresh, key)
                _, dcaches = self.draft_model.prefill_with_cache(
                    ddparams, toks, caches=dcaches, start=start,
                    seq_lens=valid)
                return nxt, caches, dcaches, key

            self._unified_jit = jax.jit(step, donate_argnums=(3, 4))
        else:
            def step(dparams, toks, caches, start, valid, fresh, key):
                self._compiles["unified"] += 1
                return trunk(dparams, toks, caches, start, valid, fresh,
                             key)

            self._unified_jit = jax.jit(step, donate_argnums=(2,))

    # -- speculative decode --------------------------------------------------

    def _resolve_draft(self) -> None:
        """Materialize the draft model: the explicit independent draft if
        one was passed, else the layer-truncated self-speculative draft
        (first ``spec_draft_layers`` blocks + shared embed/norm/head)."""
        if self.draft_model is not None:
            plan = getattr(self.draft_model, "plan", None)
            if plan is None or {k for k, _ in plan} != {"attn"}:
                raise ValueError("speculative draft must be an attention-"
                                 "only decoder stack")
            return
        n = min(self.cfg.spec_draft_layers, self.model.cfg.num_layers)
        self.draft_model, self.draft_dparams = self.model.truncate_deploy(
            self.dparams, n)

    def _build_spec_step(self):
        """One pooled speculative iteration, ONE jit:

          1. the draft autoregressively proposes k tokens per slot from
             the pending token (k+1 scan steps — the extra step ingests
             d_k so the draft cache covers every accept outcome),
          2. the trunk scores all k+1 candidate positions in a single
             verify forward (chunk-prefill prefix attend, NO cache write),
          3. rejection-sampling / greedy acceptance picks each slot's
             accepted prefix and its bonus-or-residual token,
          4. exactly the accepted prefix commits to the trunk caches
             (inactive slots commit nothing), and the draft lengths roll
             back to the committed position.

        Rejected drafts are never written to the trunk cache, so rollback
        is exact in every layout — wrapped SWA rings included, where a
        write irrecoverably destroys the evicted token."""
        k = self.cfg.spec_decode
        stochastic = self.cfg.sampler != "greedy"

        def rollback_draft(c0, c1, start, n_commit, active):
            """Restore a draft KVCache to committed state: every ring
            slot whose LAST scan-writer was a rejected position (or any
            position, for inactive slots — n_commit 0 rejects all) takes
            its pre-scan content back.  Without this, a wrapped SWA draft
            ring keeps rejected-draft K/V where evicted window tokens
            used to be, and the draft's proposals silently degrade (the
            acceptance rule keeps output exact, but the speedup erodes).
            Same last-writer-wins slot map as SPSAttention._write_chunk."""
            w = c1.k_bits.shape[2]
            s_all = jnp.arange(w)
            lv = start + k + 1                 # end of the scan's writes
            t_new = lv[:, None] - 1 - jnp.mod(
                lv[:, None] - 1 - s_all[None, :], w)           # (B, W)
            written = t_new >= start[:, None]
            rejected = written & (t_new >= (start + n_commit)[:, None])
            kc = jnp.where(rejected[:, None, :, None], c0.k_bits, c1.k_bits)
            rej_w = packing.pack_bits(rejected.astype(jnp.uint32))
            vc = ((c1.vt_bits & ~rej_w[:, None, None, :]) |
                  (c0.vt_bits & rej_w[:, None, None, :]))
            length = jnp.where(active, start + n_commit,
                               c0.length).astype(jnp.int32)
            return KVCache(kc, vc, length)

        def step(dparams, ddparams, token, caches, dcaches, start, active,
                 key):
            self._compiles["spec"] += 1
            b = token.shape[0]
            d_pre = [c["attn"] for c in dcaches if "attn" in c]

            def draft_body(carry, _):
                tok, dc, dkey = carry
                lg, dc = self.draft_model.decode_step(ddparams, tok, dc)
                dkey, sub = jax.random.split(dkey)
                nxt = self._sample(lg[:, -1:], sub)            # (B, 1)
                q = (sampler_lib.sampling_probs(
                    lg[:, -1], self.cfg.sampler, self.cfg.temperature,
                    self.cfg.top_k) if stochastic else jnp.zeros((b, 0)))
                return (nxt, dc, dkey), (nxt[:, 0], q)

            (_, dcaches, key), (drafts, qs) = jax.lax.scan(
                draft_body, (token, dcaches, key), None, length=k + 1)
            drafts_bk = jnp.swapaxes(drafts[:k], 0, 1)         # (B, k)
            chunk_toks = jnp.concatenate([token, drafts_bk], axis=1)
            logits, projs = self.model.verify_with_cache(
                dparams, chunk_toks, caches, start=start)
            if stochastic:
                key, sub = jax.random.split(key)
                out, n_acc = sampler_lib.speculative_accept(
                    drafts_bk, jnp.swapaxes(qs[:k], 0, 1), logits, sub,
                    sampler=self.cfg.sampler, temp=self.cfg.temperature,
                    k=self.cfg.top_k)
            else:
                out, n_acc = sampler_lib.speculative_accept(
                    drafts_bk, None, logits, None)
            n_commit = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
            caches = self.model.commit_chunks(caches, projs, start,
                                              n_commit)
            # draft rollback: the scan wrote positions start..start+k, of
            # which the first n_commit hold exactly the committed tokens;
            # rejected-tail slots (every slot, for inactive rows) restore
            # their pre-scan content so the draft cache always equals the
            # committed sequence — lengths AND ring bits
            it = iter(d_pre)
            dcaches = [
                dict(c, attn=rollback_draft(next(it), c["attn"], start,
                                            n_commit, active))
                if "attn" in c else c for c in dcaches]
            nxt = jnp.take_along_axis(out, n_acc[:, None], axis=1)
            return out, n_acc, nxt, caches, dcaches, key

        self._spec_jit = jax.jit(step, donate_argnums=(3, 4))

    # -- public API ---------------------------------------------------------

    def generate(self, prompts, *, max_new_tokens: int,
                 frontend_embeds: Optional[np.ndarray] = None,
                 stream_cb: Optional[Callable] = None):
        """Generate from a batch of prompts.

        prompts as a (B, S) ndarray -> static batching: returns
        (tokens (B, max_new_tokens), stats).

        prompts as a list of variable-length 1-D token arrays ->
        continuous batching over the slot pool: returns
        (list of per-prompt token arrays, stats).  ``stream_cb`` is called
        as cb(step, tokens) in static mode and cb(rid, index, token) in
        continuous mode."""
        ndim = getattr(prompts, "ndim", None)
        if ndim == 2:                             # np or jax (B, S) batch
            return self._generate_static(np.asarray(prompts),
                                         max_new_tokens,
                                         frontend_embeds, stream_cb)
        if ndim is not None and ndim != 1:
            raise ValueError(f"prompts array must be (B, S), got "
                             f"{ndim}-D; a single prompt is [prompt] or "
                             f"prompt[None, :]")
        if ndim == 1:
            raise ValueError("single 1-D prompt: pass prompt[None, :] for "
                             "static batching or [prompt] for continuous")
        if frontend_embeds is not None:
            raise ValueError("frontend models serve via the static path "
                             "(pass an equal-length (B, S) batch)")
        requests = [Request(rid=i, tokens=np.asarray(p, np.int32),
                            max_new_tokens=max_new_tokens)
                    for i, p in enumerate(prompts)]
        results, report = self.serve(requests, stream_cb=stream_cb)
        return [results[r.rid] for r in requests], report

    # -- static batching ----------------------------------------------------

    def _generate_static(self, prompts: np.ndarray, max_new_tokens: int,
                         frontend_embeds, stream_cb
                         ) -> Tuple[np.ndarray, "kvcache.EngineReport"]:
        if self.cfg.paged:
            # silently falling back to contiguous max_len rings would lose
            # the paged capacity guarantee (and wrap past max_len)
            raise ValueError(
                "paged caches serve through the continuous path: pass a "
                "list of prompts (or set ServeConfig.paged=False for the "
                "static batch path)")
        b, s = prompts.shape
        kw: Dict[str, Any] = {}
        if frontend_embeds is not None:
            kw["frontend_embeds"] = jnp.asarray(frontend_embeds)
        logits, caches = self.model.prefill_with_cache(
            self.dparams, jnp.asarray(prompts),
            max_len=self.cfg.max_len, **kw)
        if self._decode_jit is None:
            self._build_decode()
        key = jax.random.PRNGKey(self.cfg.seed)
        token = self._sample(logits, key)
        out = [np.asarray(token)]
        if stream_cb:
            stream_cb(0, out[-1])
        for t in range(1, max_new_tokens):
            token, caches, key = self._decode_jit(self.dparams, token,
                                                  caches, key)
            out.append(np.asarray(token))
            if stream_cb:
                stream_cb(t, out[-1])
        report = kvcache.cache_report(caches, seq_len=s + max_new_tokens,
                                      batch=b)
        return np.concatenate(out, axis=1), report

    # -- continuous batching ------------------------------------------------

    @property
    def _ragged_ok(self) -> bool:
        """Speculative decode needs a pure attention stack: the verify
        forward scores candidates without writing state, and recurrent
        carries have no deferred-write face.  (Chunked prefill has no
        such gate — recurrent families chunk through their carry state.)"""
        plan = getattr(self.model, "plan", None)
        return plan is not None and {k for k, _ in plan} == {"attn"}

    def _layer_rings(self, spec: PageSpec) -> List[Optional[int]]:
        """Per-layer logical ring length for paged attention caches
        (None for layers with no attention part)."""
        return [spec.ring_for(w) if kind in ("attn", "hybrid") else None
                for kind, w in getattr(self.model, "plan", [])]

    def _sync_tables(self, caches, arenas, rings):
        """Push dirty host-side block tables into the device caches.

        Each layer gets its OWN device copy of its arena's table: the
        caches pytree is donated into the jit'd step, and donation
        rejects the same buffer appearing in two leaves.  Runs once per
        iteration, before the pooled dispatch.  (No row masking: every
        pool row's writes are real under the unified step — prefill
        chunks write exactly their pages' promised content, inactive
        rows write nothing at all.)"""
        if not any(a.dirty for a in arenas.values()):
            return caches
        out = []
        for c, ring in zip(caches, rings):
            if ring is not None and isinstance(c.get("attn"), PagedKVCache):
                tbl = arenas[ring].block_tables
                c = dict(c)
                c["attn"] = c["attn"]._replace(block_table=jnp.asarray(tbl))
            out.append(c)
        for a in arenas.values():
            a.dirty = False
        return out

    def _page_keys(self, toks: np.ndarray) -> List[bytes]:
        """Hash-cons keys for the FULL pages of a prompt: key j is a
        chain digest over tokens[: (j+1) * page_size], i.e. over exactly
        the prefix that (deterministically, given the params) produces
        the page's bit-packed K/V^T words.  Equal keys => bitwise-equal
        page content, so admission can map sharers onto one physical
        page (``PageArena.set_prefix_keys`` / ``grow``)."""
        page = self.cfg.page_size
        h = hashlib.blake2b(digest_size=16)
        keys: List[bytes] = []
        toks = np.ascontiguousarray(toks, np.int32)
        for j in range(len(toks) // page):
            h.update(toks[j * page:(j + 1) * page].tobytes())
            keys.append(h.digest())
        return keys

    @staticmethod
    def _copy_pages(caches, rings, copies: Dict[int, List[Tuple[int, int]]]):
        """Apply copy-on-write page payload copies on device: for every
        layer of each affected ring group, k/vt page ``old`` duplicates
        into ``new``.  Must run before the next unified step writes any
        page (the (old, new) ids are only meaningful against the page
        contents at sweep time)."""
        out = []
        for c, ring in zip(caches, rings):
            if ring in copies and isinstance(c.get("attn"), PagedKVCache):
                # dedupe by destination, last writer wins: a COW page can
                # be freed by a preemption inside the retry loop and
                # handed to a later COW in the same sweep
                last = {}
                for old, new in copies[ring]:
                    last[new] = old
                news = jnp.asarray(list(last.keys()), jnp.int32)
                olds = jnp.asarray(list(last.values()), jnp.int32)
                pg = c["attn"]
                c = dict(c)
                c["attn"] = pg._replace(
                    k_pages=pg.k_pages.at[news].set(pg.k_pages[olds]),
                    vt_pages=pg.vt_pages.at[news].set(pg.vt_pages[olds]))
            out.append(c)
        return out

    def serve(self, requests: Sequence[Request], *,
              stream_cb: Optional[Callable] = None
              ) -> Tuple[Dict[int, np.ndarray], "kvcache.EngineReport"]:
        """Run the continuous-batching loop to completion.

        Returns ({rid: generated tokens}, stats).  Each loop iteration:
        host-side admission moves queued requests into free slots as
        in-flight prefills, paged growth covers every row's next writes
        (preempting the lowest-priority slot when the arena runs dry),
        then exactly ONE jit dispatch advances the whole pool — the
        unified chunk+decode forward when any prefill is in flight, the
        pooled decode (or speculative draft-verify-commit) step
        otherwise.  Retirement frees slots mid-flight and the next
        iteration backfills them from the queue."""
        if (getattr(self.model.cfg, "frontend_tokens", 0)
                or not hasattr(self.model, "init_caches")):
            raise ValueError("continuous batching serves decoder-only "
                             "token models")
        plan = getattr(self.model, "plan", [])
        full_attn = any(k in ("attn", "hybrid") and not w for k, w in plan)
        spec = self.cfg.page_spec() if self.cfg.paged else None
        # full-attention layers cap at the ring (contiguous: max_len) or
        # the block-table capacity (paged): a request that outgrew it
        # would silently wrap and overwrite its own oldest K/V (windowed
        # layers wrap by design — their ring IS the window)
        for r in requests:
            if len(r.tokens) == 0:
                raise ValueError(f"request {r.rid}: empty prompt "
                                 "(prefill needs at least one token)")
            if r.max_new_tokens <= 0:
                raise ValueError(f"request {r.rid}: max_new_tokens must "
                                 "be positive")
            if full_attn and len(r.tokens) + r.max_new_tokens > (
                    spec.capacity if spec else self.cfg.max_len):
                if spec:
                    raise ValueError(
                        f"request {r.rid}: prompt ({len(r.tokens)}) + "
                        f"budget ({r.max_new_tokens}) exceeds the paged "
                        f"capacity (max_blocks * page_size = "
                        f"{spec.capacity}); raise ServeConfig.max_blocks")
                raise ValueError(
                    f"request {r.rid}: prompt ({len(r.tokens)}) + budget "
                    f"({r.max_new_tokens}) exceeds the cache ring "
                    f"(max_len={self.cfg.max_len}); raise ServeConfig."
                    f"max_len")
        # serve.setup: policy, pool, page arenas and cache pools, before
        # the first iteration (device allocations land in this span)
        with jax.profiler.TraceAnnotation("serve.setup"):
            policy = (self._policy_proto if self._policy_proto is not None
                      else make_policy(self.cfg.policy))
            for r in requests:
                policy.add(r)
            pool = kvcache.SlotPool(max(1, min(self.cfg.num_slots,
                                               len(requests) or 1)))
            chunk = self.cfg.prefill_chunk
            # traffic clock + per-request latency stamps: arrival is the
            # request's open-loop offset, first/last are token emission times
            # (both on the same serve()-relative clock), so TTFT/TPOT and the
            # SLO/goodput rollup fall out at the end.  Keyed by rid, so the
            # stamps survive preemption and resume.
            t0 = time.perf_counter()
            metrics: Dict[int, Dict[str, Optional[float]]] = {
                r.rid: {"arrival": float(getattr(r, "arrival_s", 0.0)),
                        "first": None, "last": None}
                for r in requests}
            preempt_counts: Dict[str, int] = {}
            # speculative decode rides the deferred-write verify attend,
            # which is attention-only — recurrent families decode plainly
            spec_k = self.cfg.spec_decode if (self.cfg.spec_decode and
                                              self._ragged_ok) else None
            # candidate write span per pure-decode step: the pending token
            # plus the k drafted tokens (plain decode writes one position;
            # mixed unified iterations also write exactly one per decode row)
            span = (spec_k + 1) if spec_k else 1
            arenas: Dict[int, kvcache.PageArena] = {}
            rings: List[Optional[int]] = []
            if spec:
                rings = self._layer_rings(spec)
                for ring in rings:
                    if ring is None or ring in arenas:
                        continue
                    arenas[ring] = kvcache.PageArena(
                        spec.arena_pages(ring, pool.num_slots), spec.page_size,
                        pool.num_slots, spec.blocks_for_ring(ring), ring)
                caches = self.model.init_caches(pool.num_slots,
                                                self.cfg.max_len, paged=spec)
            else:
                caches = self.model.init_caches(pool.num_slots,
                                                self.cfg.max_len)
            token_buf = np.zeros((pool.num_slots, 1), np.int32)
            states: Dict[int, _SlotState] = {}
            inflight: Dict[int, _PrefillState] = {}
            results: Dict[int, np.ndarray] = {}
            resumed: Dict[int, List[int]] = {}   # rid -> tokens before preempt
            dcaches = None
            if spec_k:
                self._resolve_draft()
                # the draft pool is contiguous (a small, unshared fraction of
                # the trunk's footprint) but must cover the trunk's capacity
                draft_len = spec.capacity if spec else self.cfg.max_len
                dcaches = self.draft_model.init_caches(pool.num_slots,
                                                       draft_len)
                if self._spec_jit is None:
                    self._build_spec_step()
            if not spec_k and self._decode_jit is None:
                self._build_decode()
            if self._unified_jit is None:
                self._build_unified(bool(spec_k))
            key = jax.random.PRNGKey(self.cfg.seed)
        prefill_batches = 0  # iterations that admitted >= 1 request
        prefill_chunks = 0   # chunk advances of chunk-split prompts
        preemptions = 0
        admit_seq = 0
        iterations = 0       # engine loop passes that dispatched
        dispatches = 0       # jit calls issued — the ratio pins at 1
        spec_steps = 0
        spec_slot_steps = 0      # (active slot, verify step) pairs
        spec_drafted = 0
        spec_accepted = 0
        peak_pages = 0       # true simultaneous peak across all arenas
        passes = 0           # loop passes, dispatching or not
        # work of the pooled chunk steps (EngineReport "work" group)
        work = dict.fromkeys(("chunk_positions", "chunk_valid_positions",
                              "prefill_positions",
                              "prefill_positions_adopted"), 0)
        queue_wait: Dict[int, float] = {}   # rid -> first admission wait

        def release_slot(slot: int) -> _SlotState:
            """Shared teardown: drop python state, free the pool slot and
            every arena's pages.  Retirement and preemption differ only
            in what happens to the request afterwards."""
            st = states.pop(slot)
            pool.release(slot)
            for arena in arenas.values():
                arena.release(slot)
            return st

        def retire(slot: int) -> None:
            st = release_slot(slot)
            results[st.request.rid] = np.asarray(st.generated, np.int32)

        def preempt(slot: int) -> None:
            """Evict a slot back to the queue (recompute-on-resume): its
            pages free immediately; the prompt + tokens-so-far re-prefill
            on re-admission.  Mid-prefill slots are evictable too — their
            chunks simply recompute from the prompt on resume."""
            victim = (states.get(slot) or inflight[slot]).request
            tenant = getattr(victim, "tenant", "default")
            preempt_counts[tenant] = preempt_counts.get(tenant, 0) + 1
            policy.on_preempt(victim)
            if slot in inflight:
                st = inflight.pop(slot)
                pool.release(slot)
                for arena in arenas.values():
                    arena.release(slot)
                if st.pre:
                    resumed[st.request.rid] = list(st.pre)
                policy.requeue(st.request)
                return
            dst = release_slot(slot)
            resumed[dst.request.rid] = list(dst.generated)
            policy.requeue(dst.request)

        def pick_victim() -> int:
            """The slot minimizing ``policy.victim_key`` — default:
            lowest priority first, most recently admitted among ties,
            over decoding AND mid-prefill slots.  The policy sees each
            candidate's immediately-freeable page count (sole-owner
            pages across every arena), so ``cow_victims`` can prefer
            evictions that actually return pages."""
            def keyf(s):
                stt = states.get(s) or inflight[s]
                freeable = sum(a.freeable_pages(s)
                               for a in arenas.values())
                return policy.victim_key(stt.request, stt.admit_seq,
                                         freeable)
            return min(list(states) + list(inflight), key=keyf)

        def peak() -> None:
            nonlocal peak_pages
            peak_pages = max(peak_pages, sum(
                a.used_pages for a in arenas.values()))

        def cow_sweep(dspan: int,
                      copies: Dict[int, List[Tuple[int, int]]]) -> bool:
            """Privatize every shared page the next decode write (``dspan``
            positions per decoding slot) would diverge, recording the
            (old, new) payload copies in ``copies``; a sole owner's
            divergent write retires the page's hash key instead.  False
            when the arena has no page left for a copy (the caller
            preempts and retries)."""
            for ring, a in arenas.items():
                for slot in sorted(states):
                    base = states[slot].cache_len
                    done_lp = set()
                    for pos in range(base, base + dspan):
                        lp, page = a.write_page(slot, pos)
                        if page == 0 or lp in done_lp:
                            continue
                        done_lp.add(lp)
                        if a.refcount(page) > 1:
                            if not a.can_cow():
                                return False
                            copies.setdefault(ring, []).append(
                                a.cow(slot, lp))
                        elif a.page_key(page) is not None:
                            a.invalidate_key(page)
            return True

        def slo_endangered() -> bool:
            """True when any decoding row with a TPOT budget has gone
            more than half that budget since its last token — the
            adaptive-chunk trigger (``PolicyConfig.adaptive_chunk``)."""
            now_s = time.perf_counter() - t0
            for st in states.values():
                slo = getattr(st.request, "slo", None)
                if slo is None or slo.tpot_s is None:
                    continue
                last = metrics[st.request.rid]["last"]
                if last is not None and now_s - last > 0.5 * slo.tpot_s:
                    return True
            return False

        def plan_width() -> int:
            """Unified-step chunk width this iteration: the configured
            chunk (policy-adjusted — the SLO-adaptive hook may shrink
            it), else the power-of-two bucket covering the longest
            remaining prompt (whole prompts land in one iteration and
            the compile count stays O(log max_prompt))."""
            if chunk:
                return policy.chunk_width(chunk, slo_endangered())
            rem = max(len(st.toks) - st.done for st in inflight.values())
            return _pow2_bucket(rem)

        def emit(st: _SlotState, tok: int) -> bool:
            """Stamp latency metrics, credit the policy's fairness
            accounts, stream, and record the token; True when the
            request should retire."""
            m = metrics[st.request.rid]
            now_s = time.perf_counter() - t0
            if m["first"] is None:
                m["first"] = now_s
            m["last"] = now_s
            policy.on_tokens(st.request, 1)
            if stream_cb:
                stream_cb(st.request.rid, len(st.generated), tok)
            return st.push(tok)

        while policy or pool.active_count:
            with jax.profiler.StepTraceAnnotation("serve.iteration",
                                                  step_num=passes):
                passes += 1
                # -- admission: host bookkeeping only, no dispatch ----------
                admitted_any = False
                while policy and pool.free_count:
                    with jax.profiler.TraceAnnotation("serve.admit") as adm:
                        now_s = time.perf_counter() - t0
                        hint = chunk
                        if not hint and inflight:
                            hint = _pow2_bucket(max(
                                len(st.toks) - st.done
                                for st in inflight.values()))
                        req = policy.pop_admissible(now_s, hint)
                        if req is None:
                            break
                        adm.set_metadata(rid=req.rid)
                        pre = resumed.get(req.rid, [])
                        plen = len(req.tokens) + len(pre)
                        slot = pool.alloc(req.rid)
                        if arenas and self.cfg.prefix_share:
                            # hash-cons the prompt's full pages so this
                            # slot can adopt pages an earlier sharer
                            # already maps (and register the ones it
                            # allocates itself); resumed tokens extend the
                            # chain, so a preempted request still
                            # re-shares its original prompt prefix
                            keys = self._page_keys(np.concatenate(
                                [np.asarray(req.tokens, np.int32),
                                 np.asarray(pre, np.int32)]))
                            for arena in arenas.values():
                                arena.set_prefix_keys(slot, keys, plen)
                        # chunk-split prompts reserve only their FIRST
                        # chunk's pages now (the rest grows per
                        # iteration); whole-prompt admissions reserve
                        # prompt + first decode write — pages alone could
                        # otherwise prefill a request only for its own
                        # first growth step to preempt it straight back
                        reserve = chunk if (chunk and plen > chunk) \
                            else plen + 1
                        if arenas and not all(a.can_grow(slot, reserve)
                                              for a in arenas.values()):
                            for arena in arenas.values():
                                arena.release(slot)  # drops the promises
                            pool.release(slot)
                            policy.requeue(req)  # no pages yet; retry later
                            break
                        for arena in arenas.values():
                            arena.grow(slot, reserve)
                        toks = np.concatenate(
                            [np.asarray(req.tokens, np.int32),
                             np.asarray(resumed.pop(req.rid, []), np.int32)])
                        inflight[slot] = _PrefillState(req, toks, pre,
                                                       admit_seq)
                        policy.on_admit(req)
                        queue_wait.setdefault(
                            req.rid, now_s - metrics[req.rid]["arrival"])
                        admit_seq += 1
                        admitted_any = True
                if admitted_any:
                    prefill_batches += 1
                if not (states or inflight):
                    # open-loop idle gap: everything queued is still in
                    # the future — sleep toward the next arrival (bounded,
                    # so an arena-exhaustion requeue retries promptly)
                    # instead of spinning the admission loop
                    nxt = policy.next_arrival_s()
                    if nxt is not None:
                        gap = t0 + nxt - time.perf_counter()
                        if gap > 0:
                            with jax.profiler.TraceAnnotation("serve.wait"):
                                time.sleep(min(gap, 0.005))
                    continue
                # -- paged growth: cover every row's writes; preempt on
                # exhaustion.  Prefill rows grow to their chunk end (+ the
                # first decode write when it lands the prompt); decode
                # rows grow by the write span (1 plain/mixed; k+1
                # speculative).  The COW sweep privatizes shared pages a
                # DECODE write would diverge — prefill-chunk writes never
                # diverge a page (they write exactly the content its hash
                # key promises), so in-flight rows need neither COW nor
                # masking.
                # the iteration's unified width is planned ONCE and shared
                # by paged growth and the dispatch below: the
                # adaptive-chunk hook reads the wall clock, and growing
                # pages for one width but dispatching another could write
                # pages growth never covered
                width_now = plan_width() if inflight else 0
                if arenas:
                    copies: Dict[int, List[Tuple[int, int]]] = {}
                    with jax.profiler.TraceAnnotation("serve.grow"):
                        while states or inflight:
                            ok = True
                            dspan = span if (spec_k and not inflight) else 1
                            width = width_now if inflight else 0
                            for slot in sorted(set(states) | set(inflight)):
                                if slot in inflight:
                                    ist = inflight[slot]
                                    n = min(width, len(ist.toks) - ist.done)
                                    final = ist.done + n == len(ist.toks)
                                    target = ist.done + n + (1 if final
                                                             else 0)
                                else:
                                    target = states[slot].cache_len + dspan
                                if not all(a.grow(slot, target)
                                           for a in arenas.values()):
                                    ok = False
                                    break
                            if ok:
                                ok = cow_sweep(dspan, copies)
                            if ok:
                                break
                            preempt(pick_victim())
                            preemptions += 1
                    if not (states or inflight):
                        continue
                    if copies:
                        # apply payload copies BEFORE the step writes
                        # anything: the (old, new) ids are snapshots of
                        # the sweep-time page contents
                        with jax.profiler.TraceAnnotation("serve.copy_pages"):
                            caches = self._copy_pages(caches, rings, copies)
                    peak()
                    with jax.profiler.TraceAnnotation("serve.sync_tables"):
                        caches = self._sync_tables(caches, arenas, rings)
                # -- ONE pooled dispatch advances every in-flight stream ----
                if inflight:
                    # unified mixed iteration: prefill chunks + decode rows
                    # fused in one forward (see _build_unified)
                    width = width_now
                    with jax.profiler.TraceAnnotation("serve.prepare"):
                        toks_buf = np.zeros((pool.num_slots, width), np.int32)
                        start_buf = np.zeros((pool.num_slots,), np.int32)
                        valid_buf = np.zeros((pool.num_slots,), np.int32)
                        fresh_buf = np.zeros((pool.num_slots,), bool)
                        advance: Dict[int, int] = {}
                        for slot in sorted(inflight):
                            ist = inflight[slot]
                            n = min(width, len(ist.toks) - ist.done)
                            toks_buf[slot, :n] = \
                                ist.toks[ist.done:ist.done + n]
                            start_buf[slot] = ist.done
                            valid_buf[slot] = n
                            fresh_buf[slot] = ist.done == 0
                            advance[slot] = n
                            if arenas:
                                work["prefill_positions_adopted"] += int(
                                    np.logical_and.reduce([
                                        a.adopted_mask(slot, ist.done,
                                                       ist.done + n)
                                        for a in arenas.values()]).sum())
                        for slot in sorted(states):
                            toks_buf[slot, 0] = token_buf[slot, 0]
                            start_buf[slot] = states[slot].cache_len
                            valid_buf[slot] = 1
                        work["chunk_positions"] += toks_buf.size
                        work["chunk_valid_positions"] += int(valid_buf.sum())
                        work["prefill_positions"] += sum(advance.values())
                        toks_dev = jnp.asarray(toks_buf)
                        rows_dev = (jnp.asarray(start_buf),
                                    jnp.asarray(valid_buf),
                                    jnp.asarray(fresh_buf))
                    with jax.profiler.TraceAnnotation(
                            "serve.dispatch.chunk"):
                        if spec_k:
                            nxt, caches, dcaches, key = self._unified_jit(
                                self.dparams, self.draft_dparams, toks_dev,
                                caches, dcaches, *rows_dev, key)
                        else:
                            nxt, caches, key = self._unified_jit(
                                self.dparams, toks_dev, caches, *rows_dev,
                                key)
                    iterations += 1
                    dispatches += 1
                    with jax.profiler.TraceAnnotation("serve.readback"):
                        nxt_np = np.asarray(nxt)
                    pool.tick(busy=len(states) + len(inflight))
                    with jax.profiler.TraceAnnotation("serve.emit"):
                        # decode rows first: a decoding slot's token
                        # streams before the first token of a prefill
                        # landing its final chunk in the same forward
                        # (TTFT liveness ordering)
                        for slot in sorted(states):
                            st = states[slot]
                            st.cache_len += 1
                            tok = int(nxt_np[slot, 0])
                            token_buf[slot, 0] = tok
                            if emit(st, tok):
                                retire(slot)
                        for slot in sorted(inflight):
                            ist = inflight[slot]
                            n = advance[slot]
                            if chunk and len(ist.toks) > chunk:
                                prefill_chunks += 1
                            ist.done += n
                            if ist.done < len(ist.toks):
                                continue
                            del inflight[slot]
                            sst = _SlotState(ist.request, self.cfg.eos_id,
                                             len(ist.toks), ist.admit_seq,
                                             ist.pre)
                            states[slot] = sst
                            tok = int(nxt_np[slot, 0])
                            token_buf[slot, 0] = tok
                            if emit(sst, tok):
                                retire(slot)
                elif spec_k:
                    # pure-decode speculative iteration: draft k, verify
                    # k+1, commit the accepted prefix — one jit
                    with jax.profiler.TraceAnnotation("serve.prepare"):
                        start_buf = np.zeros((pool.num_slots,), np.int32)
                        active_buf = np.zeros((pool.num_slots,), bool)
                        for s in states:
                            start_buf[s] = states[s].cache_len
                            active_buf[s] = True
                        token_dev = jnp.asarray(token_buf)
                        start_dev = jnp.asarray(start_buf)
                        active_dev = jnp.asarray(active_buf)
                    with jax.profiler.TraceAnnotation(
                            "serve.dispatch.verify"):
                        out, n_acc, nxt, caches, dcaches, key = \
                            self._spec_jit(self.dparams, self.draft_dparams,
                                           token_dev, caches, dcaches,
                                           start_dev, active_dev, key)
                    iterations += 1
                    dispatches += 1
                    with jax.profiler.TraceAnnotation("serve.readback"):
                        out_np = np.asarray(out)
                        n_np = np.asarray(n_acc)
                        token_buf = np.asarray(nxt).copy()
                    pool.tick(busy=len(states))
                    spec_steps += 1
                    spec_slot_steps += len(states)
                    with jax.profiler.TraceAnnotation("serve.emit"):
                        for slot in sorted(states):
                            st = states[slot]
                            n = int(n_np[slot])
                            spec_drafted += spec_k
                            spec_accepted += n
                            st.cache_len += n + 1
                            for i in range(n + 1):
                                tok = int(out_np[slot, i])
                                if emit(st, tok):
                                    retire(slot)
                                    break
                        # speculative rollback, arena side: pages grown
                        # for the candidate span un-grow back to exactly
                        # the committed length (rejected-tail pages return
                        # to the free list, counted as rollback frees,
                        # never as retirements)
                        if arenas:
                            for slot in sorted(states):
                                for a in arenas.values():
                                    a.truncate(slot, states[slot].cache_len)
                else:
                    # pure-decode iteration: the dedicated pooled decode
                    # step (deploy_decode — the fused paged kernel's home)
                    with jax.profiler.TraceAnnotation("serve.prepare"):
                        token_dev = jnp.asarray(token_buf)
                    with jax.profiler.TraceAnnotation(
                            "serve.dispatch.decode"):
                        token, caches, key = self._decode_jit(
                            self.dparams, token_dev, caches, key)
                    iterations += 1
                    dispatches += 1
                    with jax.profiler.TraceAnnotation("serve.readback"):
                        toks = np.asarray(token)
                    pool.tick(busy=len(states))
                    token_buf = toks.copy()
                    with jax.profiler.TraceAnnotation("serve.emit"):
                        for slot in sorted(states):
                            st = states[slot]
                            st.cache_len += 1
                            tok = int(toks[slot, 0])
                            if emit(st, tok):
                                retire(slot)

        report = kvcache.cache_report(
            caches,
            seq_len=spec.capacity if spec else self.cfg.max_len,
            batch=pool.num_slots,
            slot_lengths=kvcache.slot_lengths(caches),
            active=[s in states for s in range(pool.num_slots)],
            busy_slot_steps=pool.busy_slot_steps,
            decode_steps=pool.decode_steps,
            arenas=list(arenas.values()) if arenas else None,
            spec_drafted=spec_drafted if spec_k else None,
            spec_accepted=spec_accepted, spec_slot_steps=spec_slot_steps,
            iterations=iterations, dispatches=dispatches,
            compiles=dict(self._compiles))
        report.prefill_batches = float(prefill_batches)
        report.prefill_chunks = float(prefill_chunks)
        report.requests = float(len(requests))
        report.spec_steps = float(spec_steps)
        report.preemptions = float(preemptions)
        for name, n in work.items():
            report[name] = float(n)
        report.queue_wait_s = queue_wait
        if spec:
            # cache_report sums per-arena peaks, which can land on
            # different steps; replace with the per-step simultaneous
            # peak the loop actually observed
            report.peak_page_utilization = (
                peak_pages / max(sum(a.num_pages
                                     for a in arenas.values()), 1))
            # peak bytes of pages actually mapped (per-arena peaks x that
            # ring group's per-layer page payload) — the figure prefix
            # sharing moves, since the arena allocation itself is static
            pb = 0.0
            for c, ring in zip(caches, rings):
                if ring is None or not isinstance(c.get("attn"),
                                                  PagedKVCache):
                    continue
                pg = c["attn"]
                per_page = 4 * (int(np.prod(pg.k_pages.shape[1:])) +
                                int(np.prod(pg.vt_pages.shape[1:])))
                pb += arenas[ring].peak_pages * per_page
            report.peak_page_bytes = float(pb)
        # -- traffic rollup: SLO attainment, goodput, per-tenant latency ----
        elapsed_s = max(time.perf_counter() - t0, 1e-9)
        good_tokens = 0
        slo_met = 0
        tstats: Dict[str, Dict[str, Any]] = {}
        for r in requests:
            m = metrics[r.rid]
            n = len(results.get(r.rid, ()))
            ttft = (m["first"] - m["arrival"]
                    if m["first"] is not None else None)
            tpot = ((m["last"] - m["first"]) / (n - 1)) if n > 1 else 0.0
            slo = getattr(r, "slo", None)
            ok = slo is None or slo.met(ttft, tpot)
            if ok:
                slo_met += 1
                good_tokens += n
            t = tstats.setdefault(getattr(r, "tenant", "default"), {
                "requests": 0.0, "tokens": 0.0, "slo_met": 0.0,
                "preemptions": 0.0, "_ttfts": []})
            t["requests"] += 1.0
            t["tokens"] += float(n)
            t["slo_met"] += 1.0 if ok else 0.0
            if ttft is not None:
                t["_ttfts"].append(ttft)
        all_ttfts: List[float] = []
        for tenant, t in tstats.items():
            t["preemptions"] = float(preempt_counts.get(tenant, 0))
            arr = t.pop("_ttfts")
            all_ttfts.extend(arr)
            t["ttft_p50_s"] = (float(np.percentile(arr, 50))
                               if arr else None)
            t["ttft_p99_s"] = (float(np.percentile(arr, 99))
                               if arr else None)
        report.elapsed_s = float(elapsed_s)
        report.goodput_under_slo = good_tokens / elapsed_s
        report.slo_attainment = slo_met / max(len(requests), 1)
        report.ttft_p50_s = (float(np.percentile(all_ttfts, 50))
                             if all_ttfts else None)
        report.ttft_p99_s = (float(np.percentile(all_ttfts, 99))
                             if all_ttfts else None)
        report.tenants = tstats
        return results, report
