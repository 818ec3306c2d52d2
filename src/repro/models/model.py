"""Top-level models: DecoderLM (dense/moe/vlm), MambaLM (ssm), ZambaLM
(hybrid), Whisper (audio enc-dec).

Uniform functional API, driven by a typed ``ExecutionPlan``
(``core/plan.py`` — phase, TP style, sequence parallelism, mesh/axes):

    init_params(key, cfg)                     -> params
    forward(params, cfg, batch, plan)         -> (logits, aux_loss, extras)
    loss_fn(params, cfg, batch, plan)         -> (loss, metrics)
    init_cache(cfg, batch, seq, dtype)        -> decode cache pytree
    decode_step / paged_decode_step(..., plan)

``plan`` accepts an ExecutionPlan, a Phase (or its string value, e.g.
"train"), or None (single device).

Layer stacks run under ``jax.lax.scan`` over stacked params (bounded HLO for
61-layer models); blocks are ``jax.checkpoint``-ed when cfg.remat.  The FAL
first-attention signal is produced by the unscanned block 0 and closed over
by the scan body (a scan-carried constant — zero recompute, DESIGN.md §7).

Tensor parallelism: ``ExecutionPlan.from_mesh(mesh)`` (tp='gspmd') runs the
forward under implicit GSPMD sharding; ``tp='explicit'`` routes the decoder
family through ``decoder_stack_tp`` — ONE shard_map over the whole block
stack in which attention/FFN kernels see their weight shards and return
partial sums, and ``blocks.block_apply`` realises the paper's per-block
collective structure (fal/parallel: one fused all-reduce; preln/falplus:
two; block 0 pays the single extra assemble for the first-attention
export).  ``sp=True`` additionally keeps inter-block activations sharded
over the model axis along the sequence (Megatron-SP LN regions): every
per-block all-reduce becomes a reduce-scatter at 1/tp the bytes, paired
with an all-gather around the LN regions — same reduce-collective count,
and block 0 still pays the one true all-reduce that exports the
first-attention signal.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fal
from repro.core.plan import ExecutionPlan, Phase
from repro.core.plan import EXPLICIT_TP_FAMILIES  # noqa: F401 (re-export)
from repro.models import attention as A
from repro.models import blocks as BL
from repro.models import layers as L
from repro.models import ssm as S
from repro.obs.trace import layer_scope


# ------------------------------------------------------------------------- #
# helpers
# ------------------------------------------------------------------------- #
def _stack_init(key, n, init_fn):
    if n == 0:
        return None
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


def _layer_kind(cfg, i):
    if cfg.n_experts and i >= cfg.first_dense_layers:
        return "moe"
    return "dense"


def cross_entropy(logits, labels, mask=None):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)


# ------------------------------------------------------------------------- #
# DecoderLM: dense / moe / vlm
# ------------------------------------------------------------------------- #
def _decoder_init(key, cfg):
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"embed": L.embed_init(ks[0], cfg.vocab, cfg.d_model,
                                               cfg.param_dtype)}
    if cfg.learned_pos:
        p["pos_emb"] = jax.random.normal(
            ks[1], (cfg.max_seq, cfg.d_model), jnp.dtype(cfg.param_dtype)) * 0.02
    p["block0"] = BL.block_init(ks[2], cfg, kind=_layer_kind(cfg, 0),
                                is_block0=True)
    n_rest = cfg.n_layers - 1
    fd = max(cfg.first_dense_layers - 1, 0) if cfg.n_experts else n_rest
    n_moe = n_rest - fd if cfg.n_experts else 0
    if fd:
        p["blocks_dense"] = _stack_init(
            ks[3], fd, lambda k: BL.block_init(k, cfg, kind="dense"))
    if n_moe:
        p["blocks_moe"] = _stack_init(
            ks[4], n_moe, lambda k: BL.block_init(k, cfg, kind="moe"))
    p["final_norm"] = L.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype)
    if not cfg.tie_embeddings:
        p["head"] = L.dense_init(ks[5], cfg.d_model, cfg.vocab, cfg.param_dtype)
    if cfg.mtp_depth:
        p["mtp"] = {
            "proj": L.dense_init(ks[6], 2 * cfg.d_model, cfg.d_model,
                                 cfg.param_dtype),
            "norm_h": L.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype),
            "norm_e": L.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype),
            # the MTP head block is a plain preln block (it sits outside the
            # main depth, so FAL's first-attention rewiring does not apply)
            "block": BL.block_init(ks[7], cfg.replace(connection="preln"),
                                   kind="dense"),
        }
    return p


def constrain_batch(x, plan: Optional[ExecutionPlan]):
    """Pin activations to batch-over-data sharding (GSPMD anchor after the
    vocab-sharded embedding gather)."""
    if plan is None or plan.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(tuple(plan.data_axes) or None, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(plan.mesh, spec))


def _embed_tokens(p, cfg, tokens, positions, image_embeds=None):
    with layer_scope("embed"):
        x = L.embed_apply(p["embed"], tokens, cfg.dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
        if cfg.learned_pos:
            x = x + p["pos_emb"].astype(x.dtype)[positions]
        if image_embeds is not None and cfg.n_image_tokens:
            n = cfg.n_image_tokens
            x = jnp.concatenate([image_embeds.astype(x.dtype), x[:, n:]],
                                axis=1)
        return x


def _logits(p, cfg, x):
    with layer_scope("head"):
        x = L.norm_apply(p["final_norm"], x, cfg.norm)
        if cfg.tie_embeddings:
            return L.unembed_apply(p["embed"], x, cfg.final_softcap)
        return L.softcap(L.dense_apply(p["head"], x), cfg.final_softcap)


def decoder_stack_tp(p, cfg, x, positions, plan: ExecutionPlan):
    """Block 0 + the scanned segments under ONE shard_map with explicit
    Megatron-style partial sums — the paper's Fig 2 on the real model.

    Weights enter through ``launch.mesh.param_specs`` (attention heads + FFN
    hidden column/row over the model axis, MoE experts over the model axis);
    activations are sharded over the data axes and — replicated over
    ``model`` by default, or sharded over ``model`` along the SEQUENCE when
    ``plan.sequence_parallel`` (Megatron-SP: the residual stream between
    blocks is (B, S/tp, D) per device).  Inside, blocks see ``plan.inner()``
    (``plan.tp_axis`` set) and compose the partial sums per
    ``core.fal.attention_must_assemble`` — fal/parallel pay one reduce
    collective per steady-state block, preln/falplus two, and the unscanned
    block 0 pays the one extra assemble that exports the first-attention
    signal; under SP each all-reduce becomes a reduce-scatter at 1/tp the
    bytes behind an all-gather of the LN region.  Returns (x, aux)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch import mesh as MX

    plan.validate(cfg)
    mesh = plan.mesh
    dax = tuple(plan.data_axes)
    max_ = plan.model_axis
    tp_size = plan.tp_size
    sp = plan.sequence_parallel
    if sp and x.shape[1] % tp_size:
        raise ValueError(
            f"sequence_parallel: seq len {x.shape[1]} is not divisible by "
            f"tp_size={tp_size} (the residual stream shards evenly or not "
            f"at all)")
    blocks = {k: p[k] for k in ("block0", "blocks_dense", "blocks_moe")
              if p.get(k) is not None}
    kv_rep = (not cfg.use_mla) and cfg.n_kv_heads % tp_size != 0
    wspecs = MX.param_specs(blocks, cfg,
                            kv_replicated=kv_rep)  # Megatron, model axis only
    inner = plan.inner()
    b_ax = dax if dax else None
    s_ax = max_ if sp else None

    def local(bp, x, positions):
        # held expert shares, the only ones to count pairs, are
        # single-device only (plan.validate)
        x, aux, _ = _run_decoder_blocks(bp, cfg, x, positions, inner)
        if dax:
            # MoE aux differs per data shard (local routing); make it the
            # global mean so the out_spec can declare it replicated
            aux = jax.lax.pmean(aux, dax)
        return x, aux

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(wspecs, P(b_ax, s_ax, None), P(b_ax, None)),
                       out_specs=(P(b_ax, s_ax, None), P()),
                       check_vma=False)
    return fn(blocks, x, positions)


def _run_decoder_blocks(p, cfg, x, positions, plan: ExecutionPlan):
    """Block 0 + the scanned dense/moe segments.  ONE implementation shared
    by the replicated/GSPMD path and the explicit-TP shard_map local body —
    the collective structure differs only through the plan the blocks see.
    Returns (x, aux, pairs): ``pairs`` (MoE layers, held experts) counts the
    pairs routed to each held expert of a held expert share; without one it
    has no columns.

    Block 0 sits outside the layer scan; without its own remat its
    attention residuals (probs etc.) are stashed for backward
    (EXPERIMENTS.md §Perf D2)."""
    wsched = BL.window_schedule(cfg)
    block0 = _maybe_remat(
        lambda pb, h: BL.block_apply(pb, cfg, h, None, positions, wsched[0],
                                     kind=_layer_kind(cfg, 0), is_block0=True,
                                     plan=plan),
        cfg)
    x, a1_raw, (aux, pairs0), _ = block0(p["block0"], x)
    pairs = [pairs0[None]]
    a1_sig = fal.first_attention_signal(cfg, p["block0"], a1_raw)

    i = 1
    for name, kind in (("blocks_dense", "dense"), ("blocks_moe", "moe")):
        if p.get(name) is not None:
            n = jax.tree.leaves(p[name])[0].shape[0]
            ws = jnp.asarray(wsched[i:i + n], jnp.int32)
            x, aux_s, pairs_s = _run_stack(p[name], cfg, x, a1_sig,
                                           positions, ws, kind, plan)
            aux += aux_s
            pairs.append(pairs_s)
            i += n
    # layers that route nothing to held experts count no pairs (no columns)
    held = [q for q in pairs if q.shape[-1]]
    return x, aux, (jnp.concatenate(held) if held
                    else jnp.zeros((0, 0), jnp.int32))


def _run_stack(p_stack, cfg, x, a1_sig, positions, windows, kind,
               plan: ExecutionPlan):
    """Scan blocks over stacked params.  Returns (x, aux_sum, pairs):
    ``pairs`` stacks each layer's pairs per held expert."""
    def body(carry, xs):
        h, aux = carry
        pb, w = xs
        h, _, (aux_i, pairs), _ = BL.block_apply(
            pb, cfg, h, a1_sig, positions, w, kind=kind, plan=plan)
        return (h, aux + aux_i), pairs

    body = _maybe_remat(body, cfg)
    (x, aux), pairs = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (p_stack, windows))
    return x, aux, pairs


def _decoder_forward(p, cfg, batch, plan: ExecutionPlan, want="logits"):
    tokens = batch["tokens"]
    B, Sq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    x = _embed_tokens(p, cfg, tokens, positions,
                      batch.get("image_embeds"))
    x = constrain_batch(x, plan)

    extras = {}
    if plan.use_explicit_tp:
        x, aux = decoder_stack_tp(p, cfg, x, positions, plan)
    else:
        x, aux, pairs = _run_decoder_blocks(p, cfg, x, positions, plan)
        if cfg.holds_expert_share:
            extras["expert_pairs"] = pairs

    if want == "hidden":
        return None, aux, dict(extras, hidden=x)
    logits = _logits(p, cfg, x)
    if cfg.mtp_depth:
        extras["hidden"] = x
    return logits, aux, extras


def _decoder_init_cache(p, cfg, batch, seq, dtype):
    B = batch
    mk = (A.mla_init_cache if cfg.use_mla else A.gqa_init_cache)
    c0 = mk(cfg, B, seq, dtype)
    rest = cfg.n_layers - 1
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (rest,) + a.shape), c0)
    return {"block0": c0, "blocks": stacked}


def _decoder_layer_stack(p, cfg, x, a1_sig, pos, blocks_cache,
                         plan: ExecutionPlan, block_tables=None,
                         n_valid=None, tok_slot=None, tok_pos=None,
                         limit=None):
    """Scan the stacked post-block0 layers in dense/moe segments over
    per-layer caches (dense+moe kinds share attention caches; the ffn kind
    switch is static per segment).  Returns (x, new_stacked_cache).

    The scan moves each layer's page pools out of the stacked cache and
    back, so it runs under the ``kv_write`` scope: what the layers compute
    carries its own (inner) scopes, and the pool slices and updates the
    scan itself adds count as K/V writes.  So do the slices of the
    layers' weights, and any op of the body no inner scope claims (the
    residual adds, where XLA leaves them unfused).

    When every layer's window is statically 0 (no sliding windows) the
    window rides into the scan body as a Python int instead of a traced
    vector — attention's static ``window == 0`` checks then hold, keeping
    the paged single-token fast path (kernels.ops.paged_decode_attention)
    live for the stacked layers, not just block 0.

    ``limit`` (static) runs only the FIRST ``limit`` stacked layers in
    depth order across the dense/moe segments — the speculative-decode
    draft's early exit.  The returned cache then stacks only those
    ``limit`` layers (None when limit == 0); the caller merges it back
    over the untouched upper slice."""
    wsched = BL.window_schedule(cfg)[1:]
    static_zero = all(isinstance(w, int) and w == 0 for w in wsched)
    ws_all = jnp.asarray(wsched, jnp.int32)
    remaining = cfg.n_layers - 1 if limit is None else limit
    i = 0
    seg_caches = []
    for name, kind in (("blocks_dense", "dense"), ("blocks_moe", "moe")):
        if remaining > 0 and name in p and p[name] is not None:
            n = jax.tree.leaves(p[name])[0].shape[0]
            n = min(n, remaining)
            remaining -= n
            pseg = p[name] if limit is None else \
                jax.tree.map(lambda a: a[:n], p[name])
            ws = None if static_zero else jax.lax.slice_in_dim(ws_all, i, i + n)
            cache_seg = jax.tree.map(
                lambda a: jax.lax.slice_in_dim(a, i, i + n), blocks_cache)

            def body(h, xs, kind=kind):
                if static_zero:
                    (pb, ci), w = xs, 0
                else:
                    pb, w, ci = xs
                h, _, _, c_new = BL.block_apply(
                    pb, cfg, h, a1_sig, None, w, kind=kind, plan=plan,
                    cache=ci, pos=pos, block_tables=block_tables,
                    n_valid=n_valid, tok_slot=tok_slot, tok_pos=tok_pos)
                return h, c_new

            xs = (pseg, cache_seg) if static_zero else \
                (pseg, ws, cache_seg)
            with layer_scope("kv_write"):
                x, cseg = jax.lax.scan(body, x, xs)
            seg_caches.append(cseg)
            i += n
    if not seg_caches:                         # limit == 0: block 0 only
        return x, None
    with layer_scope("kv_write"):
        return x, jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                               *seg_caches)


def _decoder_decode(p, cfg, batch, cache, plan: ExecutionPlan):
    tokens, pos = batch["tokens"], batch["pos"]
    positions = pos[:, None]
    x = _embed_tokens(p, cfg, tokens, positions)
    if cfg.n_image_tokens and "image_embeds" in batch:
        # VLM: while decoding through the image prefix the serving engine
        # passes the precomputed patch embedding for the current position
        x = jnp.where((pos < cfg.n_image_tokens)[:, None, None],
                      batch["image_embeds"].astype(x.dtype), x)
    wsched = BL.window_schedule(cfg)

    x, a1_raw, _, c0 = BL.block_apply(
        p["block0"], cfg, x, None, positions, wsched[0],
        kind=_layer_kind(cfg, 0), is_block0=True, plan=plan,
        cache=cache["block0"], pos=pos)
    a1_sig = fal.first_attention_signal(cfg, p["block0"], a1_raw)

    x, blocks_new = _decoder_layer_stack(p, cfg, x, a1_sig, pos,
                                         cache["blocks"], plan)
    logits = _logits(p, cfg, x)
    return logits, {"block0": c0, "blocks": blocks_new}


# ------------------------------------------------------------------------- #
# paged decode (serving engine): block-table KV cache, chunked ticks
# ------------------------------------------------------------------------- #
def _decoder_init_paged_cache(cfg, num_pages, page_size, slots, dtype,
                              kv_dtype=""):
    if cfg.use_mla:
        if kv_dtype:
            raise NotImplementedError(
                "quantized KV pages (kv_dtype) are GQA-only: the MLA cache "
                "stores latents, not per-head K/V rows")
        c0 = A.mla_init_paged_cache(cfg, num_pages, page_size, dtype)
    else:
        c0 = A.gqa_init_paged_cache(cfg, num_pages, page_size, dtype,
                                    kv_dtype=kv_dtype)
    rest = cfg.n_layers - 1
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (rest,) + a.shape), c0)
    return {
        "block0": c0, "blocks": stacked,
        # per-slot FAL export: block 1's first-attention signal at the last
        # position this slot processed.  Written every paged tick so engine
        # consumers (telemetry, and the dual-branch MHA||MLP decode dispatch
        # under plan.dual_branch) read the cached tensor instead of
        # re-running block 1's export.
        "a1_sig": jnp.zeros((slots, cfg.d_model), jnp.dtype(dtype)),
    }


def _decoder_paged_decode(p, cfg, batch, cache, plan: ExecutionPlan,
                          want="logits"):
    """Chunked paged tick: C >= 1 tokens per request against page pools.

    batch: tokens (B, C), pos (B,) PER-LANE first logical position, n_valid
    (B,) valid tokens per lane (invalid lanes -> scratch page),
    block_tables (B, T).  Returns (logits (B, C, V), new_cache).  Lanes are
    phase-independent: a C > 1 tick serves any mix of prefilling lanes
    (n_valid up to C) and decoding lanes (n_valid == 1); the serving
    engine now compiles the token-PACKED program instead (flat batch
    with ``tok_slot``/``tok_pos``); C == 1 is the retired decode-only
    tick shape.  Full attention runs the block-table
    kernels (``kernels.ops.paged_chunk_attention`` for C > 1,
    ``paged_decode_attention`` for C == 1) with no gathered HBM copy.

    With ``plan.dual_branch`` (fal/parallel-family connections only,
    ``plan.validate``) the steady-state blocks run the MHA||MLP
    branch-parallel dispatch: the MLP branch reads the cached per-slot
    first-attention signal (``cache['a1_sig']``, refreshed by block 0 at
    the top of the tick) concurrently with the attention branch's paged KV
    gather — logits are bit-identical to the sequential path whenever both
    run the same dispatch (always on the CPU fallback; the fused TPU kernel
    is tolerance-close to the unfused ops).
    """
    tokens, pos = batch["tokens"], batch["pos"]
    bt, n_valid = batch["block_tables"], batch["n_valid"]
    B, C = tokens.shape
    positions = pos[:, None] + jnp.arange(C)[None]
    x = _embed_tokens(p, cfg, tokens, positions)
    if cfg.n_image_tokens and "image_embeds" in batch:
        # VLM: patch embeddings for the chunk lanes inside the image prefix
        # (same contract as _decoder_decode, lane-wise over the chunk)
        x = jnp.where((positions < cfg.n_image_tokens)[:, :, None],
                      batch["image_embeds"].astype(x.dtype), x)
    x = constrain_batch(x, plan)
    wsched = BL.window_schedule(cfg)

    x, a1_raw, _, c0 = BL.block_apply(
        p["block0"], cfg, x, None, positions, wsched[0],
        kind=_layer_kind(cfg, 0), is_block0=True, plan=plan,
        cache=cache["block0"], pos=pos, block_tables=bt, n_valid=n_valid)
    a1_sig = fal.first_attention_signal(cfg, p["block0"], a1_raw)
    new_caches = {"block0": c0}

    # stash the per-request FAL export at each request's last valid position
    # BEFORE the steady-state stack runs; slots sitting this call out
    # (n_valid == 0) keep their cached signal
    sig = a1_sig if a1_sig is not None else a1_raw
    last = jnp.clip(n_valid - 1, 0, C - 1)
    new_sig = jnp.take_along_axis(
        sig, last[:, None, None], axis=1)[:, 0].astype(cache["a1_sig"].dtype)
    new_caches["a1_sig"] = jnp.where((n_valid > 0)[:, None], new_sig,
                                     cache["a1_sig"])

    if plan.dual_branch and a1_sig is not None and C == 1:
        # dual-branch decode tick: active lanes keep this tick's FRESH
        # activation-dtype export (bit-identical to the sequential path for
        # ANY cache dtype — routing it through the cache would round it);
        # lanes sitting the tick out read their held per-slot cached signal
        # instead of a padded lane's garbage position
        a1_sig = jnp.where((n_valid > 0)[:, None], sig[:, 0],
                           cache["a1_sig"].astype(x.dtype))[:, None, :]

    x, blocks_new = _decoder_layer_stack(p, cfg, x, a1_sig, pos,
                                         cache["blocks"], plan,
                                         block_tables=bt, n_valid=n_valid)
    new_caches["blocks"] = blocks_new

    if want == "hidden":
        # serving engines consume ONE row of logits per lane (the last
        # valid one): skip the (B, C, V) head here and let the caller run
        # ``lm_head`` on the gathered lane — at C == prefill_chunk that is
        # 1/C of the tick's dominant matmul
        return x, new_caches
    logits = _logits(p, cfg, x)
    return logits, new_caches


def _decoder_paged_packed(p, cfg, batch, cache, plan: ExecutionPlan,
                          want="logits"):
    """Token-PACKED ragged tick: one flat (T,) token buffer against page
    pools — the serving engine's ONE program per tick.

    batch: tokens (T,), tok_slot (T,) owning lane per token, tok_pos (T,)
    logical position per token (-1 = padding: scatters to scratch, emits
    meaningless rows), block_tables (S, Tb) per-SLOT tables, seg_last (S,)
    index of each slot's LAST packed token in the buffer (-1 = slot sat
    this tick out).  Returns (logits (1, T, V) — or hidden (1, T, D) with
    ``want='hidden'`` — and new_cache).

    A prefilling lane contributes up to ``chunk`` contiguous tokens and a
    decoding lane exactly one, so the tick's FLOPs scale with LIVE tokens
    instead of slots x chunk (the padded `_decoder_paged_decode` layout).
    The per-slot FAL export (``cache['a1_sig']``) is refreshed from each
    active slot's seg_last row; with ``plan.dual_branch`` the steady-state
    blocks run MHA||MLP off this tick's fresh per-token signal — every
    packed token is a live token at its own position, so no per-slot
    substitution is needed and tokens stay bit-identical to the sequential
    packed path.
    """
    tokens, bt = batch["tokens"], batch["block_tables"]
    tok_slot, tok_pos = batch["tok_slot"], batch["tok_pos"]
    seg_last = batch["seg_last"]
    positions = jnp.maximum(tok_pos, 0)[None]                   # (1, T)
    x = _embed_tokens(p, cfg, tokens[None], positions)
    if cfg.n_image_tokens and "image_embeds" in batch:
        # VLM: per-token patch embeddings for packed tokens inside the
        # image prefix (batch["image_embeds"]: (T, D))
        x = jnp.where((positions < cfg.n_image_tokens)[:, :, None],
                      batch["image_embeds"][None].astype(x.dtype), x)
    x = constrain_batch(x, plan)
    wsched = BL.window_schedule(cfg)

    x, a1_raw, _, c0 = BL.block_apply(
        p["block0"], cfg, x, None, positions, wsched[0],
        kind=_layer_kind(cfg, 0), is_block0=True, plan=plan,
        cache=cache["block0"], block_tables=bt,
        tok_slot=tok_slot, tok_pos=tok_pos)
    a1_sig = fal.first_attention_signal(cfg, p["block0"], a1_raw)
    new_caches = {"block0": c0}

    # refresh the per-slot FAL export from each active segment's LAST
    # packed token; slots sitting this tick out keep their cached signal
    sig = a1_sig if a1_sig is not None else a1_raw              # (1, T, D)
    active = seg_last >= 0
    with layer_scope("fal_signal"):
        new_sig = sig[0, jnp.maximum(seg_last, 0)].astype(
            cache["a1_sig"].dtype)
        new_caches["a1_sig"] = jnp.where(active[:, None], new_sig,
                                         cache["a1_sig"])

    x, blocks_new = _decoder_layer_stack(p, cfg, x, a1_sig, None,
                                         cache["blocks"], plan,
                                         block_tables=bt,
                                         tok_slot=tok_slot, tok_pos=tok_pos)
    new_caches["blocks"] = blocks_new

    if want == "hidden":
        # the engine reads ONE row per segment (seg_last): skip the
        # (1, T, V) head here and let the caller run ``lm_head`` on the
        # gathered segment-last rows
        return x, new_caches
    logits = _logits(p, cfg, x)
    return logits, new_caches


def _decoder_paged_packed_draft(p, cfg, batch, cache, plan: ExecutionPlan,
                                draft_blocks):
    """Early-exit packed forward for the self-speculative DRAFT path: run
    block 0 plus the first ``draft_blocks - 1`` stacked layers (depth order
    across the dense/moe segments) over the packed batch and return the
    truncated-stack hidden states — FAL's defining property (every later
    MLP reads block 0's first-attention signal, not its neighbour's
    attention) makes this shallow prefix unusually self-contained, so
    ``lm_head`` over it is the engine's draft model at ~draft_blocks /
    n_layers of the FLOPs and zero extra weights.

    Returns (hidden (1, T, D), new_cache).  K/V is scattered for the draft
    layers only — the verify pass recomputes layers < draft_blocks on the
    same tokens and overwrites those rows with identical values (the
    activations agree layer-for-layer), and is the first writer for every
    deeper layer.  ``cache['a1_sig']`` is NOT refreshed here: the per-slot
    export must track the lane's last ACCEPTED position, which only the
    verify pass knows.  Kernel dispatches traced inside carry a
    ``.draft`` site suffix so runtime telemetry separates the draft's
    attention path from the verify's."""
    from repro.kernels import ops as _ops
    tokens, bt = batch["tokens"], batch["block_tables"]
    tok_slot, tok_pos = batch["tok_slot"], batch["tok_pos"]
    positions = jnp.maximum(tok_pos, 0)[None]                   # (1, T)
    with _ops.dispatch_site_suffix("draft"):
        x = _embed_tokens(p, cfg, tokens[None], positions)
        x = constrain_batch(x, plan)
        wsched = BL.window_schedule(cfg)
        x, a1_raw, _, c0 = BL.block_apply(
            p["block0"], cfg, x, None, positions, wsched[0],
            kind=_layer_kind(cfg, 0), is_block0=True, plan=plan,
            cache=cache["block0"], block_tables=bt,
            tok_slot=tok_slot, tok_pos=tok_pos)
        a1_sig = fal.first_attention_signal(cfg, p["block0"], a1_raw)
        new_caches = {"block0": c0, "a1_sig": cache["a1_sig"]}
        x, low = _decoder_layer_stack(p, cfg, x, a1_sig, None,
                                      cache["blocks"], plan,
                                      block_tables=bt, tok_slot=tok_slot,
                                      tok_pos=tok_pos,
                                      limit=draft_blocks - 1)
    if low is None:
        new_caches["blocks"] = cache["blocks"]
    else:
        upper = jax.tree.map(
            lambda a: jax.lax.slice_in_dim(a, draft_blocks - 1, a.shape[0]),
            cache["blocks"])
        new_caches["blocks"] = jax.tree.map(
            lambda lo, hi: jnp.concatenate([lo, hi], 0), low, upper)
    return x, new_caches


def _mamba_block_init(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln": L.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype),
            "mixer": S.mamba_init(k2, cfg)}


def _mamba_init(key, cfg):
    ks = jax.random.split(key, 3)
    return {
        "embed": L.embed_init(ks[0], cfg.vocab, cfg.d_model, cfg.param_dtype),
        "blocks": _stack_init(ks[1], cfg.n_layers,
                              lambda k: _mamba_block_init(k, cfg)),
        "final_norm": L.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype),
    }


def _mamba_forward(p, cfg, batch, plan: ExecutionPlan, want="logits"):
    x = L.embed_apply(p["embed"], batch["tokens"], cfg.dtype)
    x = constrain_batch(x, plan)

    def body(h, pb):
        # pin the mixer input/output to batch-over-data sharding: without
        # the anchor GSPMD auto-spreads the SSD einsums over the idle
        # `model` axis and pays reshard collectives every layer
        # (EXPERIMENTS.md §Perf M1)
        h_in = constrain_batch(L.norm_apply(pb["ln"], h, cfg.norm), plan)
        y, _ = S.mamba_apply(pb["mixer"], cfg, h_in)
        y = constrain_batch(y, plan)
        return h + y, None

    body = _maybe_remat(body, cfg)
    x, _ = jax.lax.scan(body, x, p["blocks"])
    if want == "hidden":
        return None, jnp.zeros((), jnp.float32), {"hidden": x}
    return _logits(p, cfg, x), jnp.zeros((), jnp.float32), {}


def _mamba_init_cache(cfg, batch, seq, dtype):
    c0 = S.mamba_init_cache(cfg, batch, dtype)
    return {"blocks": jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape), c0)}


def _mamba_decode(p, cfg, batch, cache, plan: ExecutionPlan = None):
    x = L.embed_apply(p["embed"], batch["tokens"], cfg.dtype)

    def body(h, xs):
        pb, ci = xs
        y, c_new = S.mamba_decode(pb["mixer"], cfg,
                                  L.norm_apply(pb["ln"], h, cfg.norm), ci)
        return h + y, c_new

    x, new_c = jax.lax.scan(body, x, (p["blocks"], cache["blocks"]))
    return _logits(p, cfg, x), {"blocks": new_c}


# ------------------------------------------------------------------------- #
# ZambaLM (hybrid): mamba2 backbone + weight-shared attention block
# ------------------------------------------------------------------------- #
def _zamba_counts(cfg):
    n_groups = cfg.n_layers // cfg.attn_every
    trailing = cfg.n_layers - n_groups * cfg.attn_every
    return n_groups, trailing


def _zamba_init(key, cfg):
    ks = jax.random.split(key, 8)
    n_groups, trailing = _zamba_counts(cfg)
    d = cfg.d_model
    p = {
        "embed": L.embed_init(ks[0], cfg.vocab, d, cfg.param_dtype),
        # stacked (n_groups, attn_every, ...) mamba blocks
        "mamba": _stack_init(
            ks[1], n_groups,
            lambda k: _stack_init(k, cfg.attn_every,
                                  lambda k2: _mamba_block_init(k2, cfg))),
        # ONE weight-shared transformer block (zamba2); per-invocation input
        # projections concat([x, x_emb0]) -> d give invocation specificity
        "shared": BL.block_init(ks[2], cfg, kind="dense", is_block0=True),
        "in_proj": jax.random.normal(
            ks[3], (n_groups, 2 * d, d), jnp.dtype(cfg.param_dtype)) / np.sqrt(2 * d),
        "final_norm": L.norm_init(d, cfg.norm, cfg.param_dtype),
    }
    if cfg.connection in fal.NEEDS_LN_FAL:
        p["shared_ln_fal"] = L.norm_init(d, cfg.norm, cfg.param_dtype)
    if trailing:
        p["mamba_tail"] = _stack_init(
            ks[4], trailing, lambda k: _mamba_block_init(k, cfg))
    return p


def _zamba_shared_block(p, cfg, x, x0, in_proj, a1_sig, positions, *,
                        first, plan=None, cache=None, pos=None):
    """One invocation of the weight-shared attention block (FAL-aware)."""
    h_in = jnp.concatenate([x, x0], axis=-1) @ in_proj.astype(x.dtype)
    shared = dict(p["shared"])
    if "shared_ln_fal" in p:
        shared["ln_fal"] = p["shared_ln_fal"]
    out, a_raw, _, c_new = BL.block_apply(
        shared, cfg, h_in, a1_sig, positions, 0, kind="dense",
        is_block0=first, plan=plan, cache=cache, pos=pos)
    # block returns h_in + attn + mlp; zamba adds only the delta to the
    # backbone residual stream
    return x + (out - h_in), a_raw, c_new


def _zamba_forward(p, cfg, batch, plan: ExecutionPlan, want="logits"):
    tokens = batch["tokens"]
    B, Sq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    x0 = L.embed_apply(p["embed"], tokens, cfg.dtype)
    x = x0
    n_groups, trailing = _zamba_counts(cfg)

    def mamba_seg(h, pstack):
        def body(hh, pb):
            # same activation pin as MambaLM (EXPERIMENTS.md §Perf M1)
            h_in = constrain_batch(L.norm_apply(pb["ln"], hh, cfg.norm),
                                   plan)
            y, _ = S.mamba_apply(pb["mixer"], cfg, h_in)
            return hh + constrain_batch(y, plan), None
        h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, pstack)
        return h

    # group 0 (produces the first-attention signal); rematted — it sits
    # outside the group scan (EXPERIMENTS.md §Perf D2)
    def group0(p, x):
        x = mamba_seg(x, jax.tree.map(lambda a: a[0], p["mamba"]))
        return _zamba_shared_block(
            p, cfg, x, x0, p["in_proj"][0], None, positions, first=True,
            plan=plan)
    x, a1_raw, _ = _maybe_remat(group0, cfg)(p, x)
    a1_sig = fal.first_attention_signal(cfg, p["shared"], a1_raw)

    def group_body(h, xs):
        pst, iproj = xs
        h = mamba_seg(h, pst)
        h, _, _ = _zamba_shared_block(p, cfg, h, x0, iproj, a1_sig,
                                      positions, first=False, plan=plan)
        return h, None

    if n_groups > 1:
        rest = jax.tree.map(lambda a: a[1:], p["mamba"])
        x, _ = jax.lax.scan(_maybe_remat(group_body, cfg), x,
                            (rest, p["in_proj"][1:]))
    if trailing:
        x = mamba_seg(x, p["mamba_tail"])
    if want == "hidden":
        return None, jnp.zeros((), jnp.float32), {"hidden": x}
    return _logits(p, cfg, x), jnp.zeros((), jnp.float32), {}


def _zamba_init_cache(cfg, batch, seq, dtype):
    n_groups, trailing = _zamba_counts(cfg)
    mc = S.mamba_init_cache(cfg, batch, dtype)
    ac = A.gqa_init_cache(cfg, batch, seq, dtype)
    st = lambda c, n: jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), c)
    cache = {"mamba": st(st(mc, cfg.attn_every), n_groups),
             "attn": st(ac, n_groups)}
    if trailing:
        cache["mamba_tail"] = st(mc, trailing)
    return cache


def _zamba_decode(p, cfg, batch, cache, plan: ExecutionPlan):
    tokens, pos = batch["tokens"], batch["pos"]
    x0 = L.embed_apply(p["embed"], tokens, cfg.dtype)
    x = x0
    n_groups, trailing = _zamba_counts(cfg)

    def mamba_seg(h, pstack, cstack):
        def body(hh, xs):
            pb, ci = xs
            y, c_new = S.mamba_decode(pb["mixer"], cfg,
                                      L.norm_apply(pb["ln"], hh, cfg.norm), ci)
            return hh + y, c_new
        return jax.lax.scan(body, h, (pstack, cstack))

    x, mc0 = mamba_seg(x, jax.tree.map(lambda a: a[0], p["mamba"]),
                       jax.tree.map(lambda a: a[0], cache["mamba"]))
    x, a1_raw, ac0 = _zamba_shared_block(
        p, cfg, x, x0, p["in_proj"][0], None, None, first=True,
        plan=plan, cache=jax.tree.map(lambda a: a[0], cache["attn"]),
        pos=pos)
    a1_sig = fal.first_attention_signal(cfg, p["shared"], a1_raw)

    def group_body(h, xs):
        pst, iproj, mci, aci = xs
        h, mc_new = mamba_seg(h, pst, mci)
        h, _, ac_new = _zamba_shared_block(
            p, cfg, h, x0, iproj, a1_sig, None, first=False, plan=plan,
            cache=aci, pos=pos)
        return h, (mc_new, ac_new)

    new_cache = dict(cache)
    if n_groups > 1:
        rest_p = jax.tree.map(lambda a: a[1:], p["mamba"])
        rest_mc = jax.tree.map(lambda a: a[1:], cache["mamba"])
        rest_ac = jax.tree.map(lambda a: a[1:], cache["attn"])
        x, (mc_rest, ac_rest) = jax.lax.scan(
            group_body, x, (rest_p, p["in_proj"][1:], rest_mc, rest_ac))
        new_cache["mamba"] = jax.tree.map(
            lambda a, b: jnp.concatenate([a[None], b], 0), mc0, mc_rest)
        new_cache["attn"] = jax.tree.map(
            lambda a, b: jnp.concatenate([a[None], b], 0), ac0, ac_rest)
    else:
        new_cache["mamba"] = jax.tree.map(lambda a, n: a.at[0].set(n),
                                          cache["mamba"], mc0)
        new_cache["attn"] = jax.tree.map(lambda a, n: a.at[0].set(n),
                                         cache["attn"], ac0)
    if trailing:
        x, mct = mamba_seg(x, p["mamba_tail"], cache["mamba_tail"])
        new_cache["mamba_tail"] = mct
    return _logits(p, cfg, x), new_cache


# ------------------------------------------------------------------------- #
# Whisper (audio enc-dec): conv/mel frontend is a STUB — inputs are
# precomputed frame embeddings (DESIGN.md carve-out)
# ------------------------------------------------------------------------- #
def _whisper_init(key, cfg):
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p = {
        "embed": L.embed_init(ks[0], cfg.vocab, d, cfg.param_dtype),
        "pos_emb": jax.random.normal(ks[1], (cfg.max_seq, d),
                                     jnp.dtype(cfg.param_dtype)) * 0.02,
        "enc_pos": jax.random.normal(ks[2], (cfg.n_enc_frames, d),
                                     jnp.dtype(cfg.param_dtype)) * 0.02,
        "enc_block0": BL.block_init(ks[3], cfg, is_block0=True),
        "enc_blocks": _stack_init(ks[4], cfg.n_enc_layers - 1,
                                  lambda k: BL.block_init(k, cfg)),
        "enc_norm": L.norm_init(d, cfg.norm, cfg.param_dtype),
        "dec_block0": BL.block_init(ks[5], cfg, cross=True, is_block0=True),
        "dec_blocks": _stack_init(ks[6], cfg.n_layers - 1,
                                  lambda k: BL.block_init(k, cfg, cross=True)),
        "final_norm": L.norm_init(d, cfg.norm, cfg.param_dtype),
    }
    return p


def whisper_encode(p, cfg, frames, plan: ExecutionPlan = None):
    """frames: (B, F, d) stubbed frame embeddings."""
    plan = ExecutionPlan.resolve(plan).with_phase(Phase.PREFILL)
    x = frames.astype(jnp.dtype(cfg.dtype)) + p["enc_pos"].astype(
        jnp.dtype(cfg.dtype))[None, :frames.shape[1]]
    # encoder self-attention is bidirectional (causal=False), no rope
    enc0 = _maybe_remat(
        lambda pb, h: BL.block_apply(pb, cfg, h, None, None, 0,
                                     is_block0=True, plan=plan,
                                     causal=False), cfg)
    x, a1_raw, _, _ = enc0(p["enc_block0"], x)
    a1_sig = fal.first_attention_signal(cfg, p["enc_block0"], a1_raw)

    def body(h, pb):
        h, _, _, _ = BL.block_apply(pb, cfg, h, a1_sig, None, 0,
                                    plan=plan, causal=False)
        return h, None

    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, p["enc_blocks"])
    return L.norm_apply(p["enc_norm"], x, cfg.norm)


def _whisper_forward(p, cfg, batch, plan: ExecutionPlan, want="logits"):
    enc_out = whisper_encode(p, cfg, batch["frames"], plan)
    tokens = batch["tokens"]
    B, Sq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    x = L.embed_apply(p["embed"], tokens, cfg.dtype) \
        + p["pos_emb"].astype(jnp.dtype(cfg.dtype))[None, :Sq]

    dec0 = _maybe_remat(
        lambda pb, h: BL.block_apply(pb, cfg, h, None, positions, 0,
                                     is_block0=True, plan=plan,
                                     enc_out=enc_out), cfg)
    x, a1_raw, _, _ = dec0(p["dec_block0"], x)
    a1_sig = fal.first_attention_signal(cfg, p["dec_block0"], a1_raw)

    def body(h, pb):
        h, _, _, _ = BL.block_apply(pb, cfg, h, a1_sig, positions, 0,
                                    plan=plan, enc_out=enc_out)
        return h, None

    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, p["dec_blocks"])
    if want == "hidden":
        return None, jnp.zeros((), jnp.float32), {"hidden": x}
    return _logits(p, cfg, x), jnp.zeros((), jnp.float32), {}


def _whisper_init_cache(cfg, batch, seq, dtype):
    c0 = A.gqa_init_cache(cfg, batch, seq, dtype)
    rest = cfg.n_layers - 1
    return {
        "block0": c0,
        "blocks": jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (rest,) + a.shape), c0),
        "enc_out": jnp.zeros((batch, cfg.n_enc_frames, cfg.d_model),
                             jnp.dtype(dtype)),
    }


def _whisper_decode(p, cfg, batch, cache, plan: ExecutionPlan):
    tokens, pos = batch["tokens"], batch["pos"]
    enc_out = cache["enc_out"].astype(jnp.dtype(cfg.dtype))
    x = L.embed_apply(p["embed"], tokens, cfg.dtype) \
        + p["pos_emb"].astype(jnp.dtype(cfg.dtype))[pos][:, None]

    x, a1_raw, _, c0 = BL.block_apply(
        p["dec_block0"], cfg, x, None, None, 0, is_block0=True,
        plan=plan, enc_out=enc_out, cache=cache["block0"], pos=pos)
    a1_sig = fal.first_attention_signal(cfg, p["dec_block0"], a1_raw)

    def body(h, xs):
        pb, ci = xs
        h, _, _, c_new = BL.block_apply(pb, cfg, h, a1_sig, None, 0,
                                        plan=plan, enc_out=enc_out,
                                        cache=ci, pos=pos)
        return h, c_new

    x, new_c = jax.lax.scan(body, x, (p["dec_blocks"], cache["blocks"]))
    return _logits(p, cfg, x), {"block0": c0, "blocks": new_c,
                                "enc_out": cache["enc_out"]}


# ------------------------------------------------------------------------- #
# dispatch
# ------------------------------------------------------------------------- #
def init_params(key, cfg):
    if cfg.family == "ssm":
        return _mamba_init(key, cfg)
    if cfg.family == "hybrid":
        return _zamba_init(key, cfg)
    if cfg.family == "audio":
        return _whisper_init(key, cfg)
    return _decoder_init(key, cfg)


def forward(params, cfg, batch, plan=None, want="logits"):
    """Full-sequence forward -> (logits, aux_loss, extras).

    ``plan``: ExecutionPlan | Phase | phase string ("train"/"prefill") |
    None (single device, train)."""
    plan = ExecutionPlan.resolve(plan).validate(cfg)
    if not plan.full_sequence:
        raise ValueError(f"forward: phase={plan.phase.value} is not a "
                         f"full-sequence phase; use decode_step / "
                         f"paged_decode_step")
    fn = {"ssm": _mamba_forward, "hybrid": _zamba_forward,
          "audio": _whisper_forward}.get(cfg.family, _decoder_forward)
    return fn(params, cfg, batch, plan, want=want)


def init_cache(cfg, batch, seq, dtype="bfloat16"):
    if cfg.family == "ssm":
        return _mamba_init_cache(cfg, batch, seq, dtype)
    if cfg.family == "hybrid":
        return _zamba_init_cache(cfg, batch, seq, dtype)
    if cfg.family == "audio":
        return _whisper_init_cache(cfg, batch, seq, dtype)
    return _decoder_init_cache(None, cfg, batch, seq, dtype)


def decode_step(params, cfg, batch, cache, plan=None):
    """-> (logits (B,1,V), new_cache)."""
    plan = ExecutionPlan.resolve(plan).with_phase(Phase.DECODE).validate(cfg)
    fn = {"ssm": _mamba_decode, "hybrid": _zamba_decode,
          "audio": _whisper_decode}.get(cfg.family, _decoder_decode)
    return fn(params, cfg, batch, cache, plan)


PAGED_FAMILIES = ("dense", "moe", "vlm")


def init_paged_cache(cfg, num_pages, page_size, slots, dtype="bfloat16",
                     kv_dtype=""):
    """Paged-KV cache for the decoder family: (num_pages, page_size, ...)
    pools per layer + a per-slot FAL-signal buffer.  Page 0 is scratch
    (see attention.paged_scatter).  Slots are phase-independent — each
    lane's position/advance rides in per-lane ``pos``/``n_valid`` vectors,
    so one cache serves mixed prefill/decode ticks; the per-slot ``a1_sig``
    buffer is refreshed by block 0 at each lane's own last valid position
    (held for lanes sitting a tick out).

    ``kv_dtype`` selects the quantized KV page format ("" | "bf16" |
    "int8" | "fp8" — see ``attention.gqa_init_paged_cache``): int8/fp8
    pools carry per-page-row fp32 ``k_scale``/``v_scale`` pools that ride
    every downstream tree_map (stacked-layer broadcast, COW page copies,
    the spec-decode draft cache) with no further plumbing."""
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"paged KV cache: decoder family only, got {cfg.family}")
    return _decoder_init_paged_cache(cfg, num_pages, page_size, slots, dtype,
                                     kv_dtype=kv_dtype)


def paged_decode_step(params, cfg, batch, cache, plan=None, want="logits"):
    """One paged tick -> (logits, new_cache) in either paged layout:

      * token-PACKED (the serving engine's tick; selected when the batch
        carries ``tok_slot``): a flat (T,) ragged buffer with per-token
        segment ids — see ``_decoder_paged_packed`` for the contract;
        returns (1, T, V) logits / (1, T, D) hidden.
      * padded chunk (kernel/test harness layout): tokens (B, C) with
        per-lane ``pos``/``n_valid`` — see ``_decoder_paged_decode``;
        returns (B, C, V) / (B, C, D).

    ``want='hidden'`` returns the pre-head hidden states instead of logits
    — the serving engine gathers each segment's last row and runs
    ``lm_head`` on (S, 1, D), paying live-segments/T of the head matmul."""
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"paged decode: decoder family only, got {cfg.family}")
    plan = ExecutionPlan.resolve(plan).with_phase(Phase.PAGED).validate(cfg)
    if "tok_slot" in batch:
        return _decoder_paged_packed(params, cfg, batch, cache, plan,
                                     want=want)
    return _decoder_paged_decode(params, cfg, batch, cache, plan, want=want)


def lm_head(params, cfg, x):
    """Final norm + (tied) unembedding: hidden (B, S, D) -> logits
    (B, S, V).  The tail ``paged_decode_step(want='hidden')`` callers run
    on their gathered lanes."""
    return _logits(params, cfg, x)


def paged_spec_draft(params, cfg, batch, cache, plan=None, *, draft_blocks=1):
    """Self-speculative DRAFT forward on the token-packed layout: embed ->
    block 0 -> the first ``draft_blocks - 1`` stacked layers, returning
    (hidden (1, T, D), new_cache) — the early-exit stack the serving
    engine's draft loop runs ``lm_head`` over to propose tokens
    (``EngineConfig.draft_blocks``).  Requires 1 <= draft_blocks <
    cfg.n_layers; the batch contract is ``_decoder_paged_packed``'s
    (tokens/tok_slot/tok_pos/block_tables; no seg_last — the caller reads
    the rows it planted).  Draft-layer K/V is scattered; deeper layers and
    the per-slot ``a1_sig`` export are untouched (the verify pass owns
    them)."""
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"speculative draft: decoder family only, got {cfg.family}")
    if not 1 <= draft_blocks < cfg.n_layers:
        raise ValueError(
            f"draft_blocks={draft_blocks} must satisfy 1 <= draft_blocks "
            f"< n_layers={cfg.n_layers} (== n_layers would be the full "
            f"model, not a draft)")
    plan = ExecutionPlan.resolve(plan).with_phase(Phase.PAGED).validate(cfg)
    return _decoder_paged_packed_draft(params, cfg, batch, cache, plan,
                                       draft_blocks)


def lm_head_segment_tail(params, cfg, hidden, seg_last, n):
    """Per-segment multi-logit gather + head for speculative VERIFY:
    gather each segment's LAST ``n`` packed rows from ``hidden``
    (1, T, D) — rows ``seg_last[s] - (n-1) .. seg_last[s]`` — and run
    ``lm_head`` on the (S, n, D) gather, paying S*n/T of the full head.

    Returns (logits (S, n, V), rows (S, n) int32).  Lanes sitting the
    tick out (``seg_last == -1``) and gathered indices that would
    underrun row 0 are clamped to row 0 but ZEROED before the head, so
    NaN/garbage in scratch rows can never reach a sampled token —
    callers mask which columns are live (a non-speculative segment's
    only live column is the last)."""
    with layer_scope("head"):
        off = jnp.arange(n, dtype=jnp.int32) - (n - 1)           # (n,)
        rows = seg_last[:, None] + off[None, :]                  # (S, n)
        valid = (seg_last >= 0)[:, None] & (rows >= 0)
        h = hidden[0, jnp.maximum(rows, 0)]                      # (S, n, D)
        h = jnp.where(valid[:, :, None], h, 0.0)
        return _logits(params, cfg, h), rows


def copy_paged_pages(cache, src, dst, *, kernels="auto"):
    """Copy-on-write page duplication across EVERY layer's KV pools: the
    page rows at physical pages ``src`` (n,) are copied over pages ``dst``
    (n,) in block 0's pools and all stacked upper-layer pools — the device
    half of ``BlockTable`` COW (the host half swaps the block-table entry).

    The stacked ``blocks`` leaves (L-1, P, page, ...) are copied in ONE
    ``ops.copy_pages`` dispatch each by viewing them as (L-1)*P flat pages
    and offsetting the page ids per layer.  The ``a1_sig`` buffer is
    per-slot, not per-page — untouched.  Callers jit this with the cache
    donated (the Pallas path aliases the pools in place).  ``kernels`` is
    the plan's kernel path (``ExecutionPlan.kernels``)."""
    from repro.kernels import ops as _ops
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    kw = _ops.kernel_args(kernels)

    def one(pool):
        return _ops.copy_pages(pool, src, dst, **kw)

    def stacked(pool):
        L, P = pool.shape[0], pool.shape[1]
        flat = pool.reshape((L * P,) + pool.shape[2:])
        off = (jnp.arange(L, dtype=jnp.int32) * P)[:, None]
        s = (src[None, :] + off).reshape(-1)
        d = (dst[None, :] + off).reshape(-1)
        return _ops.copy_pages(flat, s, d, **kw).reshape(pool.shape)

    new = dict(cache)
    new["block0"] = jax.tree.map(one, cache["block0"])
    new["blocks"] = jax.tree.map(stacked, cache["blocks"])
    return new


def _mtp_loss(p, cfg, batch, hidden):
    """DeepSeek-V3 multi-token prediction: predict t+2 from h_t and emb_{t+1}."""
    tokens = batch["tokens"]
    emb_next = L.embed_apply(p["embed"], tokens[:, 1:], cfg.dtype)
    h = hidden[:, :-1]
    mtp = p["mtp"]
    z = jnp.concatenate([L.norm_apply(mtp["norm_h"], h, cfg.norm),
                         L.norm_apply(mtp["norm_e"], emb_next, cfg.norm)], -1)
    z = L.dense_apply(mtp["proj"], z)
    B, S1 = z.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S1)[None], (B, S1))
    z, _, _, _ = BL.block_apply(mtp["block"], cfg.replace(connection="preln"),
                                z, None, positions, 0, kind="dense")
    logits = _logits(p, cfg, z)                      # (B, S-1, V)
    return cross_entropy(logits[:, :-1], tokens[:, 2:])


def _ce_tail(p, cfg, hidden, tokens):
    with layer_scope("head"):
        logits = _logits(p, cfg, hidden)
        return cross_entropy(logits[:, :-1], tokens[:, 1:])


def loss_fn(params, cfg, batch, plan=None):
    # compute CE from the final hidden state under remat: the (B,S,V)
    # logits (+ their fp32 softmax copies) are recomputed in backward
    # instead of stashed (EXPERIMENTS.md §Perf D2)
    plan = ExecutionPlan.resolve(plan)
    _, aux, extras = forward(params, cfg, batch, plan, want="hidden")
    tokens = batch["tokens"]
    tail = jax.checkpoint(functools.partial(_ce_tail, cfg=cfg)) \
        if cfg.remat else functools.partial(_ce_tail, cfg=cfg)
    ce = tail(params, hidden=extras["hidden"], tokens=tokens)
    loss = ce + cfg.router_aux_coef * aux
    metrics = {"ce": ce, "aux": aux}
    if "expert_pairs" in extras:
        # per MoE layer: pairs routed to each held expert, and the held
        # share of all the layer's (token, expert) pairs
        pairs = extras["expert_pairs"]
        metrics["expert_pairs"] = pairs
        metrics["held_share"] = jnp.sum(pairs, -1) / (tokens.size
                                                      * cfg.top_k)
    if cfg.mtp_depth and "mtp" in params:
        mtp_fn = jax.checkpoint(functools.partial(_mtp_loss, cfg=cfg)) \
            if cfg.remat else functools.partial(_mtp_loss, cfg=cfg)
        mtp = mtp_fn(params, batch=batch, hidden=extras["hidden"])
        loss = loss + 0.3 * mtp
        metrics["mtp"] = mtp
    return loss, metrics
