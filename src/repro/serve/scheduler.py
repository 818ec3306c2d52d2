"""Continuous-batching scheduler over the paged KV cache.

Replaces the seed's fixed contiguous (B, max_seq) cache + one-token-per-tick
engine with:

  * admission control — a request enters a slot only when the page pool can
    cover its context (policy 'prompt': prompt + 1 token; 'full': prompt +
    max_new, no-preemption reservation);
  * TOKEN-PACKED ticks — the engine compiles exactly ONE jitted flat
    ``(token_budget,)`` program (``make_packed_step``) and issues ONE
    dispatch per tick that serves lanes at ANY phase over one ragged token
    buffer: token t belongs to lane ``tok_slot[t]`` at logical position
    ``tok_pos[t]``.  A prefilling lane contributes up to ``prefill_chunk``
    tokens, a decoding lane exactly one, so tick FLOPs scale with LIVE
    tokens instead of the padded slots-by-chunk rectangle (the
    segment-aware kernel ``kernels.ops.paged_packed_attention`` serves the
    attention; the LM head runs only on each segment's last token).
    Decode lanes are packed FIRST and are therefore never head-of-line
    blocked behind a prefill burst; ``EngineConfig.max_prefill_tokens`` is
    the fairness knob that additionally caps prefill tokens per tick, and
    prefill grants walk the pending lanes in rotating round-robin order
    (start index = tick counter) so no lane starves under budget pressure.
    ``pack_tokens`` is the pure host-side packer (property-tested);
  * SELF-SPECULATIVE decoding (``EngineConfig.spec_tokens``) — FAL's
    signal redirection makes the first ``draft_blocks`` blocks a built-in
    draft model: each eligible decode lane proposes n-1 tokens via the
    early-exit forward and packs the whole n-token proposal as ONE
    segment, verified by the same full-depth packed dispatch (a segment
    of length n at positions pos..pos+n-1 — per-segment causality scores
    every proposal exactly as sequential decode would).  Draft, verify
    and sampling live inside the engine's ONE jitted program per tick;
    the host accepts the longest matching proposal prefix plus the bonus
    target and rewinds rejected page growth (``BlockTable.shrink`` —
    refcount-safe, shared prefix pages survive).  Exact-match acceptance
    keeps greedy AND seeded token streams bit-identical to
    non-speculative decode;
  * per-request seeded sampling (serve/sampling.py) fused into the tick's
    dispatch — the engine picks between the reference sampler and the
    bit-exact partial-top-k fast sampler host-side per tick
    (``sampling.fast_eligible``), keeping speculative ticks from paying
    two full-vocab sorts per (lane, proposal) sample;
  * preemption by page pressure — when a slot can't grow its block table,
    the youngest other active request is evicted: its pages are released and
    it is requeued (front).  On re-admission it re-prefills prompt +
    already-generated tokens; (seed, position)-derived sampling keys make
    the resumed continuation deterministic.  Re-prefill also rebuilds the
    slot's cached first-attention signal, so dual-branch dispatch stays
    consistent across preempt -> resume;
  * dual-branch decode (``EngineConfig.dual_branch``) — under fal/parallel
    connections the steady-state blocks issue the MLP branch off the cached
    per-slot FAL signal concurrently with the paged attention gather
    (MHA||MLP, the paper's inference-side claim); bit-identical tokens;
  * radix prefix caching (``EngineConfig.prefix_cache``) — finished
    requests park their page-aligned prefixes (and the FAL ``a1_sig`` at
    the prompt's last position) in ``serve/prefix_cache.py``; admission
    longest-prefix matches the prompt, maps the cached PHYSICAL pages into
    the new request's block table (refcounted by the allocator) and enters
    prefill at the divergence point — or decode immediately on a
    full-prompt hit, with ``cache["a1_sig"]`` seeded from the entry so the
    first tick pays no block-0 assemble for the prefix.  Writes into a
    shared page copy-on-write first (``model.copy_paged_pages`` device
    memcpy + block-table swap), so a hit request can never corrupt another
    sharer's history; preemption releases only the preempted request's
    REFERENCES (shared pages survive in the tree), and its re-prefill
    restarts at the still-cached prefix instead of token 0.

The oldest active request can always claim pages from younger ones, so the
engine makes progress whenever any single request fits the pool; requests
that can never fit are rejected instead of deadlocking the queue.  Under
page pressure the relief order is: evict refcount-free prefix-cache
entries first, then preempt the youngest other request, then self.

Observability (``repro.obs``): the engine owns a ``MetricsRegistry`` —
TTFT, inter-token latency, queue wait, occupancy, page utilization and
preemptions are recorded as typed series and surfaced by ``stats()``
(p50/p99 summaries + the full registry dump under ``"metrics"``).  Pass a
``Tracer`` to additionally capture per-tick spans, per-dispatch spans
(wrapped in ``jax.profiler.TraceAnnotation`` so XLA device profiles line
up) and per-request lifecycle events (QUEUED -> ADMITTED -> PREFILL ->
DECODE -> PREEMPTED/requeued -> FINISHED) as Chrome trace-event JSON.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import ExecutionPlan, Phase
from repro.models import model as M
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, layer_scope
from repro.serve import sampling as SP
from repro.serve.paged_cache import BlockTable, PageAllocator, pages_needed
from repro.serve.prefix_cache import PrefixCache

_SITE = "serve/scheduler.py"


# --------------------------------------------------------------------------- #
# the engine's ONE jitted program
# --------------------------------------------------------------------------- #
def make_packed_step(cfg, plan=None, *, sampler=None):
    """Jitted packed tick: (params, cache, tokens (T,), tok_slot (T,),
    tok_pos (T,), block_tables (S,Tb), seg_last (S,), temps, top_ks,
    top_ps, seeds, sample_pos) -> (seg_logits (S,V), next_tokens (S,),
    new_cache).

    T is the engine's flat token budget: one ragged buffer where token t
    belongs to lane ``tok_slot[t]`` at logical position ``tok_pos[t]``
    (padding tokens carry tok_pos == -1 and never touch live state).  The
    engine consumes at most one logits row per lane, so the program runs
    the blocks to hidden states, gathers each SEGMENT's last token
    (``seg_last``, -1 for lanes sitting the tick out) and applies the LM
    head to the (S, 1, D) gather — 1/T of the tick's dominant matmul
    compared to a full (T, V) head.

    ``plan`` is a typed ``core.plan.ExecutionPlan`` — the only way to
    configure the dispatch; its phase is pinned to paged here.
    ``plan.dual_branch`` selects the MHA||MLP branch-parallel block for the
    steady-state layers (fal/parallel-family connections; validated).  The
    returned callable is phase-agnostic per SEGMENT: a prefilling lane's
    segment spans up to ``prefill_chunk`` tokens, a decoding lane's exactly
    one, in the SAME call (ONE trace, ONE dispatch per tick, FLOPs in live
    tokens).  Sampling is fused into the program (no extra dispatch) and
    the cache buffers are donated, so page pools update in place instead of
    being copied every tick.
    """
    plan = ExecutionPlan.resolve(plan).with_phase(Phase.PAGED)
    plan.validate(cfg)
    samp = sampler if sampler is not None else SP.sample_one

    def step(params, cache, tokens, tok_slot, tok_pos, block_tables,
             seg_last, temps, top_ks, top_ps, seeds, sample_pos):
        batch = {"tokens": tokens, "tok_slot": tok_slot, "tok_pos": tok_pos,
                 "block_tables": block_tables, "seg_last": seg_last}
        hidden, new_cache = M.paged_decode_step(params, cfg, batch, cache,
                                                plan, want="hidden")
        # lanes sitting the tick out carry seg_last == -1: zero their
        # gathered row BEFORE the head (a clamped row-0 gather would run
        # the LM head + sampler on another lane's scratch state — NaN or
        # garbage there must never reach a sampled token) and return the
        # -1 sentinel instead of a sampled id
        active = seg_last >= 0
        with layer_scope("head"):
            h_seg = jnp.where(active[:, None],
                              hidden[0, jnp.maximum(seg_last, 0)],
                              0.0)                                 # (S, D)
            logits = M.lm_head(params, cfg, h_seg[:, None])[:, 0]  # (S, V)
        with layer_scope("sample"):
            nxt = jax.vmap(samp)(logits, temps, top_ks, top_ps,
                                 seeds, sample_pos)
            nxt = jnp.where(active, nxt, jnp.int32(-1))
        return logits, nxt, new_cache

    return jax.jit(step, donate_argnums=(1,))


def make_spec_step(cfg, plan=None, *, spec_tokens, draft_blocks,
                   sampler=None):
    """Jitted SELF-SPECULATIVE packed tick — still ONE dispatch per tick:
    (params, cache, tokens (T,), tok_slot (T,), tok_pos (T,),
    block_tables (S,Tb), seg_last (S,), spec_mask (S,), temps, top_ks,
    top_ps, seeds) -> (targets (S,n), fed (S,n), new_cache).

    Lanes with ``spec_mask`` set are decode lanes whose packed segment
    spans ``n == spec_tokens`` rows: the lane's pending token followed by
    n-1 device-filled placeholder rows at positions pos+1..pos+n-1.  The
    program runs, inside the SAME jit trace (so the engine's host-side
    dispatch counter still increments once per tick):

      1. DRAFT — n-1 unrolled early-exit iterations.  Iteration j embeds
         each spec lane's row ``seg_start + j`` as a flat (S,) packed
         batch (non-spec lanes ride as padding, tok_pos == -1), runs
         block 0 plus the first ``draft_blocks - 1`` stacked layers
         (``model.paged_spec_draft``; FAL's signal redirection makes the
         shallow prefix its own draft model), samples a proposal with the
         SAME replayable ``fold_in(seed, position)`` key the verify pass
         will use — identical keys + near-identical logits is what makes
         seeded-sampling proposals match their targets — and plants it in
         row ``seg_start + j + 1`` of the token buffer.
      2. VERIFY — the full-depth packed forward over the whole buffer in
         the tick's one ``paged_packed_attention``-backed program: a lane
         proposing n tokens is just a segment of length n, so per-segment
         causal masking scores every proposal against exactly the context
         a sequential decode would have seen.  The LM head runs on each
         segment's last n gathered rows (``model.lm_head_segment_tail``),
         and targets are sampled per position — ``targets[s, j]`` is the
         model's true next token after row ``rows[s, j]``.

    The host accepts the longest prefix of proposals that match their
    targets (exact-match speculative sampling: greedy AND seeded streams
    stay bit-identical to non-speculative decode) plus the bonus target
    after it, then rolls rejected growth back (``BlockTable.shrink``).
    Draft-layer K/V written for rejected rows is overwritten by verify /
    later re-feeds and stays causally invisible meanwhile.  Non-spec lanes
    (prefill segments, decode lanes near ``max_seq``, lanes awaiting
    their first token) consume ``targets[s, n-1]`` — the plain packed-tick
    sample.  Dead columns are zeroed before the head and return the -1
    sentinel (same NaN-containment contract as ``make_packed_step``).

    Note ``cache['a1_sig']`` is refreshed by the verify pass from each
    segment's LAST row — for a spec lane that position may be rejected.
    No packed-engine consumer reads it for spec lanes: the dual-branch
    packed path uses the tick's fresh per-token signal, and the
    prefix-cache artifact is captured on a lane's FIRST sampled token,
    which the engine always serves non-speculatively.
    """
    n = int(spec_tokens)
    assert n >= 2, "spec_tokens >= 2 (1 proposal minimum)"
    plan = ExecutionPlan.resolve(plan).with_phase(Phase.PAGED)
    plan.validate(cfg)
    samp = sampler if sampler is not None else SP.sample_one

    def step(params, cache, tokens, tok_slot, tok_pos, block_tables,
             seg_last, spec_mask, temps, top_ks, top_ps, seeds):
        T = tokens.shape[0]
        S = seg_last.shape[0]
        seg_start = seg_last - (n - 1)
        lane = jnp.arange(S, dtype=jnp.int32)
        toks = tokens
        for j in range(n - 1):                       # ---- draft loop ----
            row = jnp.where(spec_mask, jnp.maximum(seg_start + j, 0), 0)
            dpos = jnp.where(spec_mask, tok_pos[row], -1)
            dbatch = {"tokens": jnp.where(spec_mask, toks[row], 0),
                      "tok_slot": lane, "tok_pos": dpos,
                      "block_tables": block_tables}
            dh, cache = M.paged_spec_draft(params, cfg, dbatch, cache,
                                           plan, draft_blocks=draft_blocks)
            h = jnp.where(spec_mask[:, None], dh[0], 0.0)        # (S, D)
            dlogits = M.lm_head(params, cfg, h[:, None])[:, 0]   # (S, V)
            with layer_scope("sample"):
                dnext = jax.vmap(samp)(dlogits, temps, top_ks,
                                       top_ps, seeds, dpos + 1)
            wrow = jnp.where(spec_mask, seg_start + j + 1, T)
            toks = toks.at[wrow].set(dnext, mode="drop")
        # ---- verify: the tick's ONE full-depth packed dispatch --------
        batch = {"tokens": toks, "tok_slot": tok_slot, "tok_pos": tok_pos,
                 "block_tables": block_tables, "seg_last": seg_last}
        hidden, new_cache = M.paged_decode_step(params, cfg, batch, cache,
                                                plan, want="hidden")
        logits, rows = M.lm_head_segment_tail(params, cfg, hidden,
                                              seg_last, n)      # (S, n, V)
        col = jnp.arange(n, dtype=jnp.int32)[None, :]
        live = ((seg_last >= 0)[:, None] & (rows >= 0)
                & (spec_mask[:, None] | (col == n - 1)))
        rpos = tok_pos[jnp.maximum(rows, 0)]                     # (S, n)
        one = jax.vmap(samp, in_axes=(0, None, None, None, None, 0))
        with layer_scope("sample"):
            tgt = jax.vmap(one)(logits, temps, top_ks, top_ps, seeds,
                                rpos + 1)
            tgt = jnp.where(live, tgt, jnp.int32(-1))
        fed = jnp.where(live, toks[jnp.maximum(rows, 0)], jnp.int32(-1))
        return tgt, fed, new_cache

    return jax.jit(step, donate_argnums=(1,))


@dataclasses.dataclass(frozen=True)
class PackedTick:
    """One tick's flat token plan (host-side numpy, produced by
    ``pack_tokens``).  ``tokens[t]`` is fed to lane ``tok_slot[t]`` at
    logical position ``tok_pos[t]``; the padding tail carries tok_slot == 0
    and tok_pos == -1.  ``seg_last[i]`` is the flat index of slot i's last
    token (-1 when the slot sat the tick out) and ``n_taken[i]`` how many
    tokens slot i advances; ``n_live == n_taken.sum() <= len(tokens)``."""
    tokens: np.ndarray                 # (T,) int32
    tok_slot: np.ndarray               # (T,) int32
    tok_pos: np.ndarray                # (T,) int32
    seg_last: np.ndarray               # (S,) int32
    n_taken: np.ndarray                # (S,) int32
    n_live: int


def pack_tokens(token_lists, positions, decode_flags, budget,
                prefill_cap=0, rotate=0) -> PackedTick:
    """Pure host-side token packer: per-slot lists of pending context
    tokens (empty for idle slots) at per-slot ``positions`` -> a
    ``PackedTick`` over a flat ``(budget,)`` buffer.

    Packing order and fairness:
      * decode lanes (``decode_flags[i]``) are packed FIRST, in slot order,
        and take their WHOLE pending list — one token in plain decode, or
        the lane's n-token speculative proposal — never displaced by a
        prefill burst;
      * prefill lanes then split the remaining budget (optionally capped at
        ``prefill_cap`` tokens total, 0 = uncapped) in TRUE round-robin
        order: both grant rounds walk the pending prefill lanes starting at
        slot ``rotate % slots`` (the engine passes its tick counter), so
        under sustained budget pressure every pending lane leads the grant
        order at least once every ``slots`` ticks — even as lanes join and
        leave the pending set.  A first round grants one token per lane so
        every reached lane stays live; a second round fills lanes greedily
        in the same rotated order.  (A fixed slot-0 start — the
        pre-rotation behavior — starves high-numbered lanes for as long as
        the pressure lasts.)

    Each packed slot's tokens are contiguous with monotone positions
    ``positions[i] + arange(n_taken[i])``; the buffer lays segments out in
    slot order (decode lanes first) regardless of ``rotate``.  The caller
    guarantees the budget covers every decode lane's pending list (the
    engine enforces budget >= slots * spec segment length).
    """
    S = len(token_lists)
    take = np.zeros((S,), np.int32)
    decode_ids = [i for i in range(S)
                  if len(token_lists[i]) and decode_flags[i]]
    prefill_ids = [i for i in range(S)
                   if len(token_lists[i]) and not decode_flags[i]]
    for i in decode_ids:
        take[i] = len(token_lists[i])
    left = budget - int(take.sum())
    assert left >= 0, "token budget below live decode lanes"
    if prefill_ids:
        # rotate over SLOT indices (not list positions): the start slot
        # cycles 0..S-1, so every pending lane is first in the grant order
        # at least once every S ticks even as lanes join/leave the set
        start = rotate % S
        prefill_ids = ([i for i in prefill_ids if i >= start]
                       + [i for i in prefill_ids if i < start])
    pleft = min(left, prefill_cap) if prefill_cap else left
    for i in prefill_ids:                       # round 1: liveness
        if pleft <= 0:
            break
        take[i] = 1
        pleft -= 1
    for i in prefill_ids:                       # round 2: greedy fill
        if pleft <= 0:
            break
        extra = min(len(token_lists[i]) - int(take[i]), pleft)
        take[i] += extra
        pleft -= extra
    tokens = np.zeros((budget,), np.int32)
    tok_slot = np.zeros((budget,), np.int32)
    tok_pos = np.full((budget,), -1, np.int32)
    seg_last = np.full((S,), -1, np.int32)
    off = 0
    for i in decode_ids + sorted(prefill_ids):
        n = int(take[i])
        if n == 0:
            continue
        tokens[off:off + n] = token_lists[i][:n]
        tok_slot[off:off + n] = i
        tok_pos[off:off + n] = positions[i] + np.arange(n)
        off += n
        seg_last[i] = off - 1
    return PackedTick(tokens, tok_slot, tok_pos, seg_last, take, off)


@dataclasses.dataclass
class ServeRequest:
    rid: int
    prompt: np.ndarray                 # (P,) int token ids
    max_new: int
    sampling: SP.SamplingParams = SP.SamplingParams()
    generated: list = dataclasses.field(default_factory=list)
    pos: int = 0                       # tokens of context written to cache
    done: bool = False
    truncated: bool = False            # hit the context cap / rejected
    preemptions: int = 0
    arrival: int = -1                  # submit order (preemption priority)
    submit_tick: int = -1
    finish_tick: int = -1
    # observability (wall clocks are time.perf_counter seconds)
    submit_time: float = 0.0
    queued_tick: int = -1              # last (re-)queue tick, for queue wait
    last_token_time: float = 0.0
    decoding: bool = False             # per-residency phase (reset on preempt)
    # prefix-cache plumbing (EngineConfig.prefix_cache)
    pin_prefix: bool = False           # park this prefix pinned (no eviction)
    prefix_hit_tokens: int = 0         # cached tokens mapped at last admission
    # block 1's first-attention signal at position len(prompt)-1, captured
    # the tick the first token is sampled; cached with the prefix so a
    # full-prompt hit seeds cache["a1_sig"] instead of re-running block 0
    prefix_sig: Optional[np.ndarray] = None

    def known(self) -> list:
        """Context to teacher-force: prompt + everything sampled so far."""
        return list(self.prompt) + self.generated


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Paged-engine knobs (see ROADMAP.md 'Serving')."""
    page_size: int = 16
    num_pages: int = 64                # pool size incl. scratch page 0
    slots: int = 4                     # concurrent batch lanes
    prefill_chunk: int = 16            # max prefill tokens per lane per tick
    # flat tokens per packed dispatch; 0 = auto (slots + prefill_chunk - 1:
    # one full prefill chunk plus a decode token for every other lane).
    # Must cover at least one token per slot (liveness)
    token_budget: int = 0
    # fairness knob: cap on TOTAL prefill tokens per tick so a prefill
    # burst can never crowd decode lanes out of the budget (0 = uncapped;
    # decode lanes are packed first regardless)
    max_prefill_tokens: int = 0
    max_seq: int = 256                 # per-request context cap
    admission: str = "prompt"          # 'prompt' | 'full'
    cache_dtype: str = "float32"
    # quantized KV page storage ("" | "bf16" | "int8" | "fp8"): "" keeps
    # cache_dtype pools (the legacy bit-preserved path); int8/fp8 store
    # narrow pages plus per-page-row fp32 scale pools that the paged
    # kernels dequantize at the VMEM load — ~2x concurrent requests per
    # HBM byte at equal num_pages, with greedy token streams identical to
    # bf16 on the bench workloads (see tests/test_quantized_kv.py)
    kv_dtype: str = ""
    # MHA||MLP branch-parallel decode dispatch off the cached per-slot FAL
    # signal (plan.dual_branch; fal/parallel-family connections only —
    # ExecutionPlan.validate rejects the rest).  Logits match sequential
    # decode to a few float32 ulps on the CPU dispatch path (the fused TPU
    # kernel is tolerance-close); the win is overlap of the paged KV
    # gather with the FFN matmuls.
    dual_branch: bool = False
    # radix prefix cache over page-aligned finished prefixes: admission
    # longest-prefix matches the prompt, shares the cached pages into the
    # block table (COW on write) and seeds the FAL a1_sig on full-prompt
    # hits.  max_cached_prefix_pages caps the tree's own page budget
    # (0 = bounded only by the pool; LRU eviction under pressure either way)
    prefix_cache: bool = False
    max_cached_prefix_pages: int = 0
    # self-speculative decoding (the FAL early-exit draft): spec_tokens is
    # the tokens each decode lane PROPOSES per tick (its packed segment
    # length; 0 = off, >= 2 on), draft_blocks how many leading blocks
    # (block 0 included) the draft path runs before its LM head.  The
    # draft, the verify and the fused sampling all live in the engine's
    # ONE jitted dispatch per tick; exact-match acceptance keeps greedy
    # and seeded token streams bit-identical to non-speculative decode
    spec_tokens: int = 0
    draft_blocks: int = 2


class PagedEngine:
    """Slot-based continuous batching over paged KV (decoder family).

    ``metrics``: a ``repro.obs.MetricsRegistry`` (one is created per engine
    when omitted — benchmarks driving several engines keep their series
    separate).  ``tracer``: a ``repro.obs.Tracer``; the default NULL tracer
    records nothing and costs one no-op context per span site."""

    def __init__(self, cfg, params, engine_cfg: EngineConfig = EngineConfig(),
                 plan=None, metrics: Optional[MetricsRegistry] = None,
                 tracer=None):
        if cfg.family not in M.PAGED_FAMILIES:
            raise NotImplementedError(cfg.family)
        if cfg.n_image_tokens:
            # model.paged_decode_step supports image_embeds, but the engine's
            # request/step plumbing is text-only — refuse rather than serve
            # image prefixes as text tokens (silently wrong logits)
            raise NotImplementedError(
                "PagedEngine serves text-only requests; vlm image prefixes "
                "need image_embeds plumbed through ServeRequest")
        assert engine_cfg.admission in ("prompt", "full"), engine_cfg.admission
        self.cfg, self.params, self.ecfg = cfg, params, engine_cfg
        self.spec = int(engine_cfg.spec_tokens)
        if self.spec:
            if self.spec < 2:
                raise ValueError(
                    f"spec_tokens={self.spec}: needs >= 2 (the lane's "
                    f"pending token + at least one proposal), or 0 = off")
            if not 1 <= engine_cfg.draft_blocks < cfg.n_layers:
                raise ValueError(
                    f"draft_blocks={engine_cfg.draft_blocks} must satisfy "
                    f"1 <= draft_blocks < n_layers={cfg.n_layers}")
        # every decode lane needs spec_tokens rows under speculation; the
        # auto budget generalises slots + chunk - 1 accordingly
        seg = max(1, self.spec)
        self.budget = engine_cfg.token_budget or (
            engine_cfg.slots * seg + engine_cfg.prefill_chunk - 1)
        if self.budget < engine_cfg.slots * seg:
            raise ValueError(
                f"token_budget={self.budget} cannot keep all "
                f"{engine_cfg.slots} slots live (need >= slots * "
                f"{seg} packed rows per decode lane)")
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # the engine stores a typed plan, not a context dict; every jitted
        # dispatch it compiles runs under this plan with phase=paged
        self.plan = ExecutionPlan.resolve(plan).with_phase(Phase.PAGED)
        if engine_cfg.dual_branch:
            self.plan = self.plan.with_dual_branch()
        self.plan.validate(cfg)
        self.max_blocks = pages_needed(engine_cfg.max_seq,
                                       engine_cfg.page_size)
        self.cache = M.init_paged_cache(
            cfg, engine_cfg.num_pages, engine_cfg.page_size,
            engine_cfg.slots, engine_cfg.cache_dtype,
            kv_dtype=engine_cfg.kv_dtype)
        # two sampler variants of the one jitted program, built lazily:
        # the fast partial-top-k sampler when every lane's params qualify
        # (SP.fast_eligible, checked host-side per tick), the full-sort
        # reference otherwise — either way ONE dispatch per tick
        self._step_fns = {}
        # HBM bytes per page across every layer's pools (scale pools
        # included, a1_sig excluded) — the allocator turns page pressure
        # into byte pressure (engine_kv_bytes_in_use / stats()["page_bytes"])
        page_bytes = sum(
            leaf.size * leaf.dtype.itemsize // engine_cfg.num_pages
            for leaf in jax.tree.leaves(
                {k: self.cache[k] for k in ("block0", "blocks")}))
        self.allocator = PageAllocator(engine_cfg.num_pages,
                                       engine_cfg.page_size,
                                       metrics=self.metrics,
                                       page_bytes=page_bytes)
        self.tables = [BlockTable(self.allocator, self.max_blocks)
                       for _ in range(engine_cfg.slots)]
        self.pcache: Optional[PrefixCache] = None
        self._cow_fn = None
        if engine_cfg.prefix_cache:
            self.pcache = PrefixCache(
                self.allocator, max_pages=engine_cfg.max_cached_prefix_pages,
                metrics=self.metrics, tracer=self.tracer)
            # per-page device memcpy across every layer's pools; the cache
            # is donated so the Pallas path rewrites the pools in place
            self._cow_fn = jax.jit(
                functools.partial(M.copy_paged_pages,
                                  kernels=self.plan.kernels),
                donate_argnums=(0,))
        self.slots: List[Optional[ServeRequest]] = [None] * engine_cfg.slots
        self.queue: List[ServeRequest] = []
        self.finished: List[ServeRequest] = []
        self.ticks = 0
        self.packed_calls = 0
        self.dispatches = 0
        self.dispatch_ticks = 0        # ticks that issued >= 1 dispatch
        self._arrival = 0
        # registered up front so reset()/export enumerate a stable set
        self._c_ticks = self.metrics.counter(
            "engine_ticks_total", unit="ticks", site=_SITE)
        self._c_dispatches = self.metrics.counter(
            "engine_dispatches_total", unit="calls", site=_SITE)
        self._c_packed = self.metrics.counter(
            "engine_packed_calls_total", unit="calls", site=_SITE)
        self._c_prefill_toks = self.metrics.counter(
            "engine_prefill_tokens_total", unit="tokens", site=_SITE)
        self._c_decode_toks = self.metrics.counter(
            "engine_decode_tokens_total", unit="tokens", site=_SITE)
        self._c_preempt = self.metrics.counter(
            "engine_preemptions_total", unit="events", site=_SITE)
        self._c_rejected = self.metrics.counter(
            "engine_rejected_total", unit="events", site=_SITE)
        self._c_admitted = self.metrics.counter(
            "engine_admitted_total", unit="events", site=_SITE)
        self._c_finished = self.metrics.counter(
            "engine_finished_total", unit="events", site=_SITE)
        self._h_occ = self.metrics.histogram(
            "engine_occupancy", unit="ratio", site=_SITE)
        self._h_util = self.metrics.histogram(
            "engine_page_utilization", unit="ratio", site=_SITE)
        self._h_queue_wait = self.metrics.histogram(
            "engine_queue_wait_ticks", unit="ticks", site=_SITE)
        self._h_ttft_ms = self.metrics.histogram(
            "engine_ttft_ms", unit="ms", site=_SITE)
        self._h_ttft_ticks = self.metrics.histogram(
            "engine_ttft_ticks", unit="ticks", site=_SITE)
        self._h_itl_ms = self.metrics.histogram(
            "engine_inter_token_ms", unit="ms", site=_SITE)
        self._h_req_ticks = self.metrics.histogram(
            "engine_request_latency_ticks", unit="ticks", site=_SITE)
        self._h_dispatch_ms = self.metrics.histogram(
            "engine_dispatch_ms", unit="ms", site=_SITE)
        self._h_tok_disp = self.metrics.histogram(
            "engine_tokens_per_dispatch", unit="tokens", site=_SITE)
        self._h_pad_frac = self.metrics.histogram(
            "engine_padding_fraction", unit="ratio", site=_SITE)
        self._c_cow = self.metrics.counter(
            "engine_cow_copies_total", unit="pages", site=_SITE)
        self._c_sig_seeded = self.metrics.counter(
            "engine_a1_sig_seeded_total", unit="events", site=_SITE)
        self._h_ttft_hit_ms = self.metrics.histogram(
            "engine_ttft_hit_ms", unit="ms", site=_SITE)
        self._h_ttft_cold_ms = self.metrics.histogram(
            "engine_ttft_cold_ms", unit="ms", site=_SITE)
        self._h_ttft_hit_ticks = self.metrics.histogram(
            "engine_ttft_hit_ticks", unit="ticks", site=_SITE)
        self._h_ttft_cold_ticks = self.metrics.histogram(
            "engine_ttft_cold_ticks", unit="ticks", site=_SITE)
        self._c_spec_acc = self.metrics.counter(
            "engine_spec_accepted_total", unit="tokens", site=_SITE)
        self._c_spec_rej = self.metrics.counter(
            "engine_spec_rejected_total", unit="tokens", site=_SITE)
        self._h_spec_len = self.metrics.histogram(
            "engine_spec_accepted_len", unit="tokens", site=_SITE)

    # ------------------------------------------------------------------ #
    def submit(self, req: ServeRequest):
        req.arrival = self._arrival
        self._arrival += 1
        req.submit_tick = self.ticks
        req.queued_tick = self.ticks
        req.submit_time = time.perf_counter()
        self.queue.append(req)
        self.tracer.begin_async("req", req.rid, prompt_len=len(req.prompt),
                                max_new=req.max_new)
        self.tracer.instant("QUEUED", rid=req.rid)

    def _admission_pages(self, r: ServeRequest) -> int:
        ctx = len(r.known())
        ahead = ctx + (r.max_new - len(r.generated)) \
            if self.ecfg.admission == "full" else ctx + 1
        return pages_needed(min(ahead, self.ecfg.max_seq),
                            self.ecfg.page_size)

    def _reject(self, r: ServeRequest):
        r.done = r.truncated = True
        r.finish_tick = self.ticks
        self._c_rejected.inc()
        self.finished.append(r)
        self.tracer.instant("REJECTED", rid=r.rid)
        self.tracer.end_async("req", r.rid, outcome="rejected")

    def _admit(self):
        while self.queue:
            try:
                free = self.slots.index(None)
            except ValueError:
                return
            r = self.queue[0]
            ctx = len(r.known())
            need = self._admission_pages(r)
            # requests that can never complete are rejected instead of
            # deadlocking the queue (or livelocking the pool): the context
            # must fit max_seq with room to sample at least one token, and
            # its pages must fit the pool
            if (ctx + 1 > self.ecfg.max_seq
                    or need > min(self.max_blocks, self.allocator.capacity)):
                self.queue.pop(0)
                self._reject(r)
                continue
            # longest-prefix match; the provisional ``share`` keeps matched
            # pages at refcount > 1 through any eviction below, so a
            # just-matched node can never be freed out from under us
            n_hit, hit_pages, hit_a1 = 0, [], {}
            if self.pcache is not None and ctx > 1:
                n_hit, hit_pages, hit_a1 = self.pcache.match(
                    np.asarray(r.known(), np.int64))
                if hit_pages:
                    self.allocator.share(hit_pages)
            need_new = need - len(hit_pages)
            if not self.allocator.can_alloc(need_new):
                if self.pcache is not None:
                    self.pcache.evict(need_new - self.allocator.free_pages)
                if not self.allocator.can_alloc(need_new):
                    if hit_pages:           # drop the provisional hold
                        self.allocator.free(hit_pages)
                    return                   # FCFS: no head-of-line skipping
            self.queue.pop(0)
            # (re-)prefill from the divergence point; a full-prompt hit
            # (n_hit == ctx) enters decode on its first tick — the last
            # prompt token runs as a one-token decode segment (its page is
            # COW'd out of the shared span before the write)
            r.pos = min(n_hit, ctx - 1)
            r.decoding = False
            r.prefix_hit_tokens = n_hit
            self.tables[free].adopt(hit_pages)
            self.slots[free] = r
            self._c_admitted.inc()
            self._h_queue_wait.record(self.ticks - r.queued_tick)
            self.tracer.instant("ADMITTED", rid=r.rid, slot=free,
                                wait_ticks=self.ticks - r.queued_tick)
            if self.pcache is not None:
                self.pcache.note_admission(n_hit)
            if n_hit:
                self.tracer.instant("PREFIX_HIT", rid=r.rid, slot=free,
                                    hit_tokens=n_hit,
                                    shared_pages=len(hit_pages))
                # seed the FAL signal from the cached entry on decode
                # entry: the paper's redirected first-attention output at
                # position pos is a pure function of tokens [0, pos], so
                # the stored artifact replaces block 0's assemble
                if r.pos == ctx - 1 and r.pos in hit_a1:
                    sig = jnp.asarray(hit_a1[r.pos],
                                      self.cache["a1_sig"].dtype)
                    self.cache["a1_sig"] = \
                        self.cache["a1_sig"].at[free].set(sig)
                    self._c_sig_seeded.inc()
            self.tracer.instant("PREFILL", rid=r.rid, slot=free,
                                context=ctx, from_pos=r.pos)
            if self.ecfg.admission == "full":
                # reservation policy: actually hold the worst-case pages now
                # so this request can never be preempted for page pressure
                ok = self.tables[free].ensure(
                    min(ctx + r.max_new - len(r.generated),
                        self.ecfg.max_seq))
                assert ok                    # can_alloc(need) just passed

    # ------------------------------------------------------------------ #
    def _preempt(self, i: int):
        r = self.slots[i]
        # release() drops this request's REFERENCES only: pages shared with
        # the prefix cache stay allocated (the tree's refcount holds them),
        # so re-admission longest-prefix matches the still-cached prefix
        # and re-prefills from the divergence point, not token 0
        self.tables[i].release()
        r.pos = 0
        r.decoding = False
        r.preemptions += 1
        r.queued_tick = self.ticks
        self._c_preempt.inc()
        self.slots[i] = None
        self.queue.insert(0, r)              # front: resumes before new work
        self.tracer.instant("PREEMPTED", rid=r.rid, slot=i,
                            generated=len(r.generated))

    def _pick_victim(self, exclude: int) -> Optional[int]:
        cands = [i for i, r in enumerate(self.slots)
                 if r is not None and i != exclude]
        if not cands:
            return None
        return max(cands, key=lambda i: self.slots[i].arrival)  # youngest

    def _relieve_pressure(self, exclude: int) -> bool:
        """Free page capacity under pressure, cheapest first: evict
        refcount-free prefix-cache entries (no recompute lost — only idle
        cached prefixes), then preempt the youngest other active request.
        False => nothing left to take (caller must preempt itself)."""
        if self.pcache is not None and self.pcache.evict(1):
            return True
        victim = self._pick_victim(exclude=exclude)
        if victim is None:
            return False
        self._preempt(victim)
        return True

    def _ensure(self, i: int, new_len: int) -> bool:
        """Grow slot i's block table to cover new_len tokens AND privatise
        (copy-on-write) any prefix-shared page in this tick's write range
        [pos, new_len), relieving page pressure as needed.  False => slot i
        was itself preempted (or finished truncated) and is gone."""
        if pages_needed(new_len, self.ecfg.page_size) \
                > min(self.max_blocks, self.allocator.capacity):
            # infeasible no matter how many victims are evicted (would
            # livelock the while-loop below): finish truncated instead
            self._finish(i, truncated=True)
            return False
        while not self.tables[i].ensure(new_len):
            if not self._relieve_pressure(exclude=i):
                self._preempt(i)
                return False
        if self.pcache is None:
            return True
        # COW: the packed tick will scatter K/V for positions [pos,
        # new_len); any page there still shared with the tree (or another
        # sharer) gets a private device copy first, so the write can never
        # leak into another request's history.  Only the divergence
        # boundary page is ever shared, so this runs at most once per
        # admission in steady state.
        r = self.slots[i]
        while True:
            blk = self.tables[i].first_shared_block(r.pos, new_len)
            if blk is None:
                return True
            got = self.allocator.alloc(1)
            if got is None:
                if not self._relieve_pressure(exclude=i):
                    self._preempt(i)
                    return False
                continue
            old = self.tables[i].pages[blk]
            with self.tracer.span("engine.cow", annotate=True,
                                  page_from=old, page_to=got[0]):
                self.cache = self._cow_fn(
                    self.cache, jnp.asarray([old], jnp.int32),
                    jnp.asarray([got[0]], jnp.int32))
            self.tables[i].replace(blk, got[0])
            self._c_cow.inc()
            self.tracer.instant("COW", rid=r.rid, slot=i, block=blk,
                                page_from=old, page_to=got[0])

    def _park_prefix(self, i: int, r: ServeRequest):
        """Insert the finished request's page-aligned written prefix (and
        its captured a1_sig at the prompt's last position) into the radix
        tree.  Runs BEFORE ``release()``: ``insert`` takes the tree's own
        refcount on newly-cached pages, release then drops the table's."""
        ps = self.ecfg.page_size
        n_ins = (r.pos // ps) * ps       # only fully-written pages
        if n_ins <= 0:
            return
        a1 = {}
        q = len(r.prompt) - 1
        if r.prefix_sig is not None and q < n_ins:
            a1[q] = r.prefix_sig
        adopted = self.pcache.insert(
            np.asarray(r.known()[:n_ins], np.int64),
            self.tables[i].pages[:n_ins // ps], a1=a1,
            pinned=r.pin_prefix)
        if adopted:
            self.tracer.instant("PREFIX_PARKED", rid=r.rid,
                                pages=adopted, tokens=n_ins)

    def _finish(self, i: int, truncated: bool = False):
        r = self.slots[i]
        if self.pcache is not None:
            self._park_prefix(i, r)
        r.done = True
        r.truncated = truncated
        r.finish_tick = self.ticks
        self.tables[i].release()
        self.slots[i] = None
        self.finished.append(r)
        self._c_finished.inc()
        self._h_req_ticks.record(r.finish_tick - r.submit_tick)
        self.tracer.instant("FINISHED", rid=r.rid, truncated=truncated,
                            generated=len(r.generated))
        self.tracer.end_async(
            "req", r.rid, outcome="truncated" if truncated else "finished")

    # ------------------------------------------------------------------ #
    def _spec_eligible(self, r: ServeRequest) -> bool:
        """A decode lane speculates when (a) speculation is on, (b) it has
        already sampled its first token — the first-token tick runs
        non-speculatively so the prefix-cache ``a1_sig`` artifact is
        captured at the prompt's true last position — and (c) a full
        n-token proposal fits under ``max_seq`` (no variable-length spec
        segments: near the cap the lane falls back to plain decode)."""
        return (self.spec > 0 and len(r.generated) > 0
                and r.pos + self.spec <= self.ecfg.max_seq)

    def _plan_pack(self) -> PackedTick:
        """Pack this tick's pending context into one flat token buffer:
        each active lane offers up to ``prefill_chunk`` tokens when
        prefilling — granted in rotating round-robin order (the tick
        counter advances the start index, so no pending lane starves
        under budget pressure) — or, when decoding, its pending token
        plus ``spec_tokens - 1`` placeholder rows the device's draft loop
        fills in; ``pack_tokens`` fits them into the engine's token
        budget, decode lanes first."""
        lists, poss, dec = [], [], []
        for r in self.slots:
            if r is None:
                lists.append([])
                poss.append(0)
                dec.append(False)
                continue
            decoding = len(r.known()) - r.pos == 1
            if decoding and self._spec_eligible(r):
                # the lane's one pending token + n-1 placeholders: rows
                # pos+1..pos+n-1 are proposed ON DEVICE by the draft loop
                lists.append(r.known()[r.pos:] + [0] * (self.spec - 1))
            else:
                lists.append(r.known()[r.pos:r.pos + self.ecfg.prefill_chunk])
            poss.append(r.pos)
            dec.append(decoding)
        return pack_tokens(lists, poss, dec, self.budget,
                           self.ecfg.max_prefill_tokens, rotate=self.ticks)

    def _consume_one(self, i: int, tok: int, now: float):
        """Append one sampled token to lane i (the plain packed-tick emit
        path: first-token artifacts, TTFT/ITL series, finish checks)."""
        r = self.slots[i]
        r.generated.append(tok)
        if len(r.generated) == 1:
            if self.pcache is not None and r.prefix_sig is None:
                # block 1's first-attention signal at position
                # len(prompt)-1 (this tick's seg_last row), the
                # prefix artifact _park_prefix caches at finish
                r.prefix_sig = np.asarray(self.cache["a1_sig"][i])
            ttft_ms = (now - r.submit_time) * 1e3
            ttft_ticks = self.ticks - r.submit_tick
            self._h_ttft_ms.record(ttft_ms)
            self._h_ttft_ticks.record(ttft_ticks)
            if self.pcache is not None:
                hot = r.prefix_hit_tokens > 0
                (self._h_ttft_hit_ms if hot
                 else self._h_ttft_cold_ms).record(ttft_ms)
                (self._h_ttft_hit_ticks if hot
                 else self._h_ttft_cold_ticks).record(ttft_ticks)
        elif r.last_token_time:
            self._h_itl_ms.record((now - r.last_token_time) * 1e3)
        r.last_token_time = now
        if not r.decoding:
            r.decoding = True
            self.tracer.instant("DECODE", rid=r.rid, slot=i,
                                generated=len(r.generated))
        if len(r.generated) >= r.max_new:
            self._finish(i)
        elif len(r.known()) >= self.ecfg.max_seq:
            self._finish(i, truncated=True)

    def _consume_spec_lane(self, i: int, tgt_row: np.ndarray,
                           fed_row: np.ndarray, now: float):
        """Accept the longest prefix of lane i's n-1 proposals that match
        their verify targets, plus the bonus target after it; rewind the
        rejected growth.  ``tgt_row[j]`` is the model's true token at
        position pos+j+1, ``fed_row[j]`` what was packed at position
        pos+j (row 0 the real pending token, rows 1.. the proposals)."""
        n = self.spec
        r = self.slots[i]
        a = 0
        while a < n - 1 and int(fed_row[a + 1]) == int(tgt_row[a]):
            a += 1
        # the emitted stream must be exactly what sequential decode would
        # produce, truncated at the same finish boundaries
        room = min(r.max_new - len(r.generated),
                   self.ecfg.max_seq - len(r.known()))
        emit = [int(t) for t in tgt_row[:a + 1][:room]]
        r.generated.extend(emit)
        # positions pos..pos+len(emit)-1 now hold verified context; the
        # trailing rejected rows' pages are rewound (shrink drops only
        # THIS table's references — shared prefix pages survive).  Their
        # K/V stays causally invisible until the positions are re-fed.
        r.pos += len(emit)
        dropped = self.tables[i].shrink(r.pos)
        self._c_decode_toks.inc(len(emit))
        self._c_spec_acc.inc(a)
        self._c_spec_rej.inc(n - 1 - a)
        self._h_spec_len.record(len(emit))
        if dropped:
            self.tracer.instant("SPEC_ROLLBACK", rid=r.rid, slot=i,
                                pages=dropped, accepted=a)
        if r.last_token_time:
            self._h_itl_ms.record((now - r.last_token_time) * 1e3)
        r.last_token_time = now
        if not r.decoding:
            r.decoding = True
            self.tracer.instant("DECODE", rid=r.rid, slot=i,
                                generated=len(r.generated))
        if len(r.generated) >= r.max_new:
            self._finish(i)
        elif len(r.known()) >= self.ecfg.max_seq:
            self._finish(i, truncated=True)

    def _step_for(self, fast):
        """The tick's jitted program with the fast or reference sampler
        fused in (at most two compiled variants per engine)."""
        if fast not in self._step_fns:
            samp = SP.fast_sampler(self.cfg.vocab) if fast else None
            if self.spec:
                self._step_fns[fast] = make_spec_step(
                    self.cfg, self.plan, spec_tokens=self.spec,
                    draft_blocks=self.ecfg.draft_blocks, sampler=samp)
            else:
                self._step_fns[fast] = make_packed_step(
                    self.cfg, self.plan, sampler=samp)
        return self._step_fns[fast]

    def _run_packed(self, pt: PackedTick):
        """One jitted engine call (forward + fused sampling) over a packed
        token buffer; consume samples for every request whose context
        completed this call.  Lanes may be in DIFFERENT phases: lane i
        advances its ``pt.n_taken[i]`` packed tokens (under speculation a
        decode lane's segment spans its whole n-token proposal and may
        emit up to n tokens).  The plain and the speculative tick share
        one path through the ``engine.upload``, ``engine.dispatch``,
        ``engine.sync`` and ``engine.consume`` spans; the plain tick waits
        for the device only when some lane completes its context."""
        S = self.ecfg.slots
        tr = self.tracer
        ids = [i for i in range(S) if pt.n_taken[i] > 0]
        self.dispatches += 1
        self._c_dispatches.inc()
        self._h_occ.record(len(ids) / S)
        T = pt.tokens.shape[0]
        self._h_tok_disp.record(pt.n_live)
        self._h_pad_frac.record(1.0 - pt.n_live / T)
        with tr.span("engine.upload", annotate=True):
            bt = np.stack([t.as_row() for t in self.tables])
            temps = np.zeros((S,), np.float32)
            ks = np.zeros((S,), np.int32)
            ps = np.ones((S,), np.float32)
            seeds = np.zeros((S,), np.int32)
            poss = np.zeros((S,), np.int32)
            spec_mask = np.zeros((S,), bool)
            for i in ids:
                r = self.slots[i]
                sp = r.sampling
                temps[i], ks[i], ps[i] = sp.temperature, sp.top_k, sp.top_p
                seeds[i] = sp.seed
                # position of the would-be new token (== len(known())
                # exactly when this call completes the request's context)
                poss[i] = r.pos + int(pt.n_taken[i])
                # a decode lane whose segment spans > 1 row is speculating
                # (only _plan_pack's spec-eligible lanes pack that way)
                spec_mask[i] = (len(r.known()) - r.pos == 1
                                and int(pt.n_taken[i]) > 1)
            step_fn = self._step_for(all(
                SP.fast_eligible(self.slots[i].sampling, self.cfg.vocab)
                for i in ids))
            # the speculative program takes the mask where the plain one
            # takes the sample positions (it samples at each row's own)
            tail = (spec_mask, temps, ks, ps, seeds) if self.spec \
                else (temps, ks, ps, seeds, poss)
            args = [jnp.asarray(a) for a in
                    (pt.tokens, pt.tok_slot, pt.tok_pos, bt, pt.seg_last)
                    + tail]
        t0 = time.perf_counter()
        with tr.span("engine.dispatch", annotate=True, lanes=len(ids),
                     live_tokens=pt.n_live, budget=T,
                     spec_lanes=int(spec_mask.sum())):
            # plain: (seg_logits, next tokens); speculative: (targets,
            # fed tokens)
            out_a, out_b, self.cache = step_fn(self.params, self.cache,
                                               *args)
        # does this call complete some lane's context (a token to read)?
        done = any(self.slots[i].pos + int(pt.n_taken[i])
                   == len(self.slots[i].known()) for i in ids)
        with tr.span("engine.sync", annotate=True):
            got = ((np.asarray(out_a), np.asarray(out_b)) if self.spec
                   else (np.asarray(out_b),) if done else ())
        self._h_dispatch_ms.record((time.perf_counter() - t0) * 1e3)
        now = time.perf_counter()
        with tr.span("engine.consume", annotate=True):
            for i in ids:
                r = self.slots[i]
                adv = int(pt.n_taken[i])
                if spec_mask[i]:
                    # pos/decode-token accounting live inside the helper:
                    # only the ACCEPTED prefix advances the lane
                    self._consume_spec_lane(i, got[0][i], got[1][i], now)
                    continue
                if len(r.known()) - r.pos == 1:
                    self._c_decode_toks.inc(adv)
                else:
                    self._c_prefill_toks.inc(adv)
                r.pos += adv
                if r.pos == len(r.known()):
                    # the plain sample, or the speculative verify pass's
                    # last column (the plain packed-tick sample at pos)
                    tok = got[0][i][self.spec - 1] if self.spec \
                        else got[0][i]
                    self._consume_one(i, int(tok), now)

    # ------------------------------------------------------------------ #
    def step(self):
        """One engine tick: admit, then ONE packed dispatch serving every
        active lane at its own phase."""
        self.ticks += 1
        self._c_ticks.inc()
        with self.tracer.span("engine.tick", annotate=True,
                              tick=self.ticks):
            with self.tracer.span("engine.admit", annotate=True):
                self._admit()
            d0 = self.dispatches
            self._step_packed()
            if self.dispatches > d0:
                self.dispatch_ticks += 1
            self._h_util.record(self.allocator.stats()["utilization"])

    def _step_packed(self):
        """ONE flat (token_budget,) dispatch: prefilling lanes advance up
        to ``prefill_chunk`` packed tokens, decoding lanes 1 (or pack
        their whole n-token speculative proposal), in the same jitted
        call.  Page growth (``_ensure``) can preempt or
        truncate lanes mid-plan; every eviction frees budget, so the pack
        is re-planned until the surviving lanes' plan sticks (each
        non-final iteration empties at least one slot, bounding the loop
        at slots + 1)."""
        with self.tracer.span("engine.plan", annotate=True):
            pt = self._plan_packed()
        if pt is not None:
            self.packed_calls += 1
            self._c_packed.inc()
            self._run_packed(pt)

    def _plan_packed(self) -> Optional[PackedTick]:
        """The tick's pack once every packed lane's pages are ensured, or
        None when no lane has a token to feed."""
        for _ in range(self.ecfg.slots + 1):
            pt = self._plan_pack()
            if pt.n_live == 0:
                return None
            replan = False
            for i in range(self.ecfg.slots):
                if pt.n_taken[i] == 0 or self.slots[i] is None:
                    continue
                if not self._ensure(i, self.slots[i].pos
                                    + int(pt.n_taken[i])):
                    replan = True             # slot i preempted/truncated
                    break
            # _ensure can also evict OTHER packed lanes as victims
            if not replan and all(
                    self.slots[i] is not None
                    for i in range(self.ecfg.slots) if pt.n_taken[i] > 0):
                return pt
        return None

    def run(self, max_ticks: Optional[int] = None) -> List[ServeRequest]:
        while any(s is not None for s in self.slots) or self.queue:
            if max_ticks is not None and self.ticks >= max_ticks:
                break
            self.step()
        return self.finished

    # ------------------------------------------------------------------ #
    def reset_stats(self):
        """Zero every counter/series (and drop buffered trace events) while
        keeping compiled programs, live requests and page state (benchmarks
        call this after warmup)."""
        self.ticks = 0
        self.packed_calls = 0
        self.dispatches = self.dispatch_ticks = 0
        self.metrics.reset()
        self.tracer.clear()
        self.allocator.peak_in_use = self.allocator.in_use

    def stats(self) -> dict:
        frag = sum(self.tables[i].internal_fragmentation(self.slots[i].pos)
                   for i in range(self.ecfg.slots)
                   if self.slots[i] is not None)

        def pcts(h):
            return {"p50": h.percentile(50), "p99": h.percentile(99),
                    "mean": h.mean, "count": h.count}

        return {
            "ticks": self.ticks,
            "packed_calls": self.packed_calls,
            "dispatches": self.dispatches,
            "dispatch_ticks": self.dispatch_ticks,
            # over ticks that issued any dispatch (a tick whose only lane
            # was truncated/preempted mid-growth legitimately issues
            # none): EXACTLY 1.0 under packed ticks
            "dispatches_per_tick":
                self.dispatches / max(self.dispatch_ticks, 1),
            # active lanes per dispatch / slots: packed ticks keep every
            # occupied lane advancing in every dispatch (modulo the
            # prefill-token fairness cap)
            "mean_occupancy": self._h_occ.mean,
            # the tentpole metrics: live tokens per flat dispatch and the
            # fraction of the buffer burned as padding (the padded layout
            # pays ~ 1 - (slots + chunk - 1)/(slots * chunk) here)
            "token_budget": self.budget,
            "tokens_per_dispatch": pcts(self._h_tok_disp),
            "padding_fraction": pcts(self._h_pad_frac),
            "prefill_tokens": self._c_prefill_toks.value,
            "decode_tokens": self._c_decode_toks.value,
            "preemptions": self._c_preempt.value,
            "rejected": self._c_rejected.value,
            "mean_page_utilization": self._h_util.mean,
            "internal_fragmentation": frag,
            "pages": self.allocator.stats(),
            # request-lifecycle latency summaries (the registry is the
            # source of truth; these are the headline cuts)
            "ttft_ms": pcts(self._h_ttft_ms),
            "ttft_ticks": pcts(self._h_ttft_ticks),
            "inter_token_ms": pcts(self._h_itl_ms),
            "queue_wait_ticks": pcts(self._h_queue_wait),
            "request_latency_ticks": pcts(self._h_req_ticks),
            "dispatch_ms": pcts(self._h_dispatch_ms),
            # prefix-sharing cut (None when EngineConfig.prefix_cache off):
            # radix-tree contents + hit rates, allocator sharing, COW and
            # a1_sig seeding counts, and TTFT split hot (prefix hit at
            # admission) vs cold
            # self-speculative decoding cut (None when spec_tokens == 0):
            # proposal acceptance counts/rate and the per-tick emitted
            # (accepted + bonus) length distribution — mean accepted_len
            # is the tokens-per-tick multiplier over plain decode
            "spec": None if not self.spec else {
                "spec_tokens": self.spec,
                "draft_blocks": self.ecfg.draft_blocks,
                "proposals_accepted": self._c_spec_acc.value,
                "proposals_rejected": self._c_spec_rej.value,
                "acceptance_rate": self._c_spec_acc.value / max(
                    self._c_spec_acc.value + self._c_spec_rej.value, 1),
                "accepted_len": pcts(self._h_spec_len),
            },
            "prefix": None if self.pcache is None else {
                **self.pcache.stats(),
                "shared_pages": self.allocator.shared_pages,
                "cow_copies": self._c_cow.value,
                "a1_sig_seeded": self._c_sig_seeded.value,
                "ttft_hit_ms": pcts(self._h_ttft_hit_ms),
                "ttft_cold_ms": pcts(self._h_ttft_cold_ms),
                "ttft_hit_ticks": pcts(self._h_ttft_hit_ticks),
                "ttft_cold_ticks": pcts(self._h_ttft_cold_ticks),
            },
            "metrics": self.metrics.to_dict(),
        }

    @property
    def preemptions(self) -> int:
        return self._c_preempt.value

    @property
    def rejected(self) -> int:
        return self._c_rejected.value
