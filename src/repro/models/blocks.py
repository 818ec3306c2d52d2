"""Transformer block assembly honoring the FAL connection modes (core/fal.py).

A block is:  x + MHA(ln1(x)) + FFN(mlp_input)   with optional gemma2-style
post-norms, MoE FFN, MLA attention, and cross-attention (whisper decoder).

Execution is driven by an ``ExecutionPlan`` (core/plan.py): ``plan.phase``
picks the full-sequence / decode / paged attention path, and inside the
explicit-TP shard_map (``plan.tp_axis`` set) this module owns the paper's
per-block collective structure — including the Megatron-SP sequence-parallel
variant (``plan.sequence_parallel``) where the residual stream between
blocks stays sharded over the model axis along the sequence dimension and
every all-reduce becomes a reduce-scatter (1/tp the reduce bytes) paired
with an all-gather around the LN regions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import fal
from repro.core.plan import ExecutionPlan, Phase
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.obs.trace import layer_scope
from repro.optim import grad_compress as GC


def block_init(key, cfg, *, kind="dense", cross=False, is_block0=False):
    """kind: 'dense' (cfg.mlp FFN) | 'moe'.  cross adds cross-attention."""
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p = {"ln1": L.norm_init(d, cfg.norm, cfg.param_dtype),
         "ln2": L.norm_init(d, cfg.norm, cfg.param_dtype)}
    p["attn"] = (A.mla_init(ks[0], cfg) if cfg.use_mla
                 else A.gqa_init(ks[0], cfg))
    if cross:
        p["ln_x"] = L.norm_init(d, cfg.norm, cfg.param_dtype)
        p["xattn"] = A.gqa_init(ks[1], cfg, cross=True)
    if kind == "moe":
        p["ffn"] = M.moe_init(ks[2], cfg)
    else:
        d_ff = cfg.dense_d_ff or cfg.d_ff
        p["ffn"] = L.mlp_init(ks[2], d, d_ff, cfg.mlp, cfg.param_dtype)
    if cfg.connection in fal.NEEDS_LN_FAL and (
            not is_block0 or cfg.connection == "ablation1"):
        # ablation1 normalises each block's OWN attention — block 0 included
        p["ln_fal"] = L.norm_init(d, cfg.norm, cfg.param_dtype)
    if is_block0 and cfg.connection == "fal":
        p["ln_a"] = L.norm_init(d, cfg.norm, cfg.param_dtype)  # footnote 3
    if cfg.post_norms:
        p["post_attn"] = L.norm_init(d, cfg.norm, cfg.param_dtype)
        p["post_ffn"] = L.norm_init(d, cfg.norm, cfg.param_dtype)
    return p


def _assemble(partial, axis, compress="none"):
    """All-reduce a TP partial sum over ``axis``; identity when replicated.
    tp_size = 1 is the degenerate psum — one code path, not two.

    ``compress`` (``plan.grad_compress``) selects the BACKWARD collective:
    'none' is a plain psum (its transpose — the TP gradient all-reduce —
    stays exact fp32, byte-identical HLO to before the knob existed);
    'int8'/'lowrank' route the cotangent through
    ``optim.grad_compress.compressed_psum`` — forward still exact."""
    if axis is None:
        return partial
    with layer_scope("assemble"):
        if compress != "none":
            return GC.compressed_psum(partial, axis, compress)
        return jax.lax.psum(partial, axis)


def _self_attention(p, cfg, x, positions, window, plan: ExecutionPlan, *,
                    causal=True, cache=None, pos=None, block_tables=None,
                    n_valid=None, tok_slot=None, tok_pos=None):
    """ln1 and the self-attention of ``plan.phase`` (full sequence,
    single-token decode, paged padded or token-packed), under the ``attn``
    scope.  Returns (a, new_cache); ``new_cache`` is None for the
    full-sequence phases."""
    with layer_scope("attn"):
        h = L.norm_apply(p["ln1"], x, cfg.norm)
        if plan.phase is Phase.PAGED:
            if tok_slot is not None:
                if cfg.use_mla:
                    return A.mla_packed_apply(p["attn"], cfg, h, cache,
                                              block_tables, tok_slot, tok_pos)
                return A.gqa_packed_apply(p["attn"], cfg, h, cache,
                                          block_tables, tok_slot, tok_pos,
                                          window=window, kernels=plan.kernels)
            if cfg.use_mla:
                return A.mla_paged_apply(p["attn"], cfg, h, cache,
                                         block_tables, pos, n_valid)
            return A.gqa_paged_apply(p["attn"], cfg, h, cache, block_tables,
                                     pos, n_valid, window=window,
                                     kernels=plan.kernels)
        if plan.phase is Phase.DECODE:
            if cfg.use_mla:
                return A.mla_decode(p["attn"], cfg, h, cache, pos)
            return A.gqa_decode(p["attn"], cfg, h, cache, pos, window=window)
        if cfg.use_mla:
            return A.mla_apply(p["attn"], cfg, h, positions, plan=plan), None
        return A.gqa_apply(p["attn"], cfg, h, positions, window=window,
                           causal=causal, plan=plan), None


def _mlp_input(cfg, p, x, a, a1_sig, is_block0):
    """The block's MLP input under the connection mode (``core.fal``),
    under the ``fal_signal`` scope."""
    with layer_scope("fal_signal"):
        if is_block0:
            return fal.block0_mlp_input(cfg, p, x, a)
        return fal.mlp_input(cfg, p, x, a, a1_sig)


def _no_aux():
    """``aux`` of a layer that routes nothing: (balance loss 0, no pairs)."""
    return jnp.zeros((), jnp.float32), jnp.zeros((0,), jnp.int32)


def _ffn_apply(p, cfg, h, kind, plan: ExecutionPlan):
    """Returns (y, aux), under the ``mlp`` scope, ``aux`` = (balance loss,
    pairs routed to each held expert): the pairs of a held expert share
    (single device only, ``plan.validate``), else empty.  Under explicit
    TP ``y`` is a PARTIAL sum (dense: column-sharded wi/wg, row-sharded
    wo; MoE: local experts only)."""
    with layer_scope("mlp"):
        if kind != "moe":
            return L.mlp_apply(p["ffn"], h, cfg.mlp), _no_aux()
        axis = plan.tp_axis
        if axis is not None:
            y, aux = M.moe_apply_partial(p["ffn"], cfg, h, axis)
        elif plan.is_training_like and plan.is_sharded:
            fn = (M.moe_apply_shard_slot if cfg.route_groups
                  else M.moe_apply_sharded)
            y, aux = fn(p["ffn"], cfg, h, plan)
        elif cfg.holds_expert_share:
            return M.moe_apply_held(p["ffn"], cfg, h)
        else:
            y, aux = M.moe_apply(p["ffn"], cfg, h)
        return y, (aux, jnp.zeros((0,), jnp.int32))


def block_apply(p, cfg, x, a1_sig, positions, window, *, kind="dense",
                is_block0=False, plan=None, enc_out=None, cache=None,
                pos=None, causal=True, block_tables=None, n_valid=None,
                tok_slot=None, tok_pos=None):
    """One block, full-sequence (train/eval/prefill), single-token decode,
    or paged decode/prefill (``plan.phase``; ``cache`` a page pool).  Paged
    has two layouts: the padded chunk layout — x (B, C, D) with per-lane
    ``pos``/``n_valid`` — and the token-PACKED layout — x (1, T, D) one
    flat ragged buffer with per-token ``tok_slot``/``tok_pos`` segment ids
    (selected when ``tok_slot`` is not None; the serving engine's tick).

    Returns (x_out, a_raw, aux, new_cache).  ``a_raw`` is this block's MHA
    output (block 0 exports it as the first-attention signal); ``aux`` is
    ``_ffn_apply``'s (balance loss, pairs per held expert).

    Inside the explicit-TP shard_map (``plan.tp_axis`` set) the attention
    and FFN kernels see head-/hidden-/expert-sharded weights and return
    PARTIAL sums; this function owns the paper's collective structure:
    modes whose MLP input needs this block's assembled attention
    (``fal.attention_must_assemble``) pay two all-reduces, everything else
    adds the MHA and MLP partials locally and pays ONE fused all-reduce
    (Fig 2's 2 -> 1 halving).  With tp_size = 1 the psums are identity and
    this is exactly the replicated path — one code path for the family.
    ``a_raw`` is a partial sum on the fused path (no fused-path caller
    consumes it: fal/falplus block 0 always assemble).

    With ``plan.sequence_parallel`` the same fork runs in the Megatron-SP
    layout (``_block_apply_sp``): x arrives sharded (B, S/tp, D) along the
    sequence over the model axis and every all-reduce above becomes a
    reduce-scatter (1/tp the bytes) behind an all-gather of the LN region.
    """
    plan = ExecutionPlan.resolve(plan)
    if plan.dual_branch and not is_block0 \
            and plan.phase in (Phase.DECODE, Phase.PAGED):
        # steady-state MHA||MLP branch parallelism (plan.validate guarantees
        # a DUAL_BRANCH_MODES connection and no post-norms); block 0 stays
        # sequential — it must assemble its attention to export the signal
        if "xattn" in p:
            raise NotImplementedError(
                "dual-branch decode supports self-attention decoder blocks "
                "only (cross-attention consumes the assembled attention)")
        return _block_apply_dual(p, cfg, x, a1_sig, window, kind=kind,
                                 plan=plan, cache=cache, pos=pos,
                                 block_tables=block_tables, n_valid=n_valid,
                                 tok_slot=tok_slot, tok_pos=tok_pos)
    if plan.sequence_parallel and plan.tp_axis is not None \
            and plan.full_sequence:
        if "xattn" in p or not causal:
            # cross-attention consumes the assembled attention and the
            # encoder stacks are bidirectional — neither has an SP layout;
            # refuse rather than silently fuse/skip them
            raise NotImplementedError(
                "sequence-parallel blocks support causal self-attention "
                "only (no cross-attention / bidirectional encoders)")
        return _block_apply_sp(p, cfg, x, a1_sig, positions, window,
                               kind=kind, is_block0=is_block0, plan=plan)

    a, new_cache = _self_attention(p, cfg, x, positions, window, plan,
                                   causal=causal, cache=cache, pos=pos,
                                   block_tables=block_tables,
                                   n_valid=n_valid, tok_slot=tok_slot,
                                   tok_pos=tok_pos)
    axis = plan.tp_axis
    # post-norms and cross-attention normalise/consume the true ``a`` —
    # nonlinear in the partial, so they force the assembled path
    fused = (axis is not None and not cfg.post_norms and "xattn" not in p
             and not fal.attention_must_assemble(cfg.connection, is_block0))

    if fused:
        # MLP input is independent of this block's attention: add the MHA
        # and MLP partial sums locally, assemble both in ONE all-reduce
        mlp_in = _mlp_input(cfg, p, x, a, a1_sig, is_block0)
        y, aux = _ffn_apply(p, cfg, mlp_in, kind, plan)
        return (x + _assemble(a + y, axis, plan.grad_compress),
                a, aux, new_cache)

    a = _assemble(a, axis, plan.grad_compress)
    if cfg.post_norms:
        a = L.norm_apply(p["post_attn"], a, cfg.norm)

    resid = x + a

    if "xattn" in p:  # whisper decoder cross-attention
        with layer_scope("attn"):
            cx = A.gqa_cross_apply(p["xattn"], cfg,
                                   L.norm_apply(p["ln_x"], resid, cfg.norm),
                                   enc_out)
        cx = _assemble(cx, axis, plan.grad_compress)
        resid = resid + cx
        x = x + cx  # the FAL mlp_input uses x without self-attn but with cross

    mlp_in = _mlp_input(cfg, p, x, a, a1_sig, is_block0)

    y, aux = _ffn_apply(p, cfg, mlp_in, kind, plan)
    y = _assemble(y, axis, plan.grad_compress)
    if cfg.post_norms:
        y = L.norm_apply(p["post_ffn"], y, cfg.norm)
    return resid + y, a, aux, new_cache


def _block_apply_dual(p, cfg, x, a1_sig, window, *, kind,
                      plan: ExecutionPlan, cache, pos, block_tables,
                      n_valid, tok_slot=None, tok_pos=None):
    """Branch-parallel decode block: MHA || MLP (``plan.dual_branch``).

    For ``core.fal.DUAL_BRANCH_MODES`` the MLP input is a function of only
    the residual stream and the (cached) first-attention signal — never this
    block's own attention — so the two branches share no data dependency:

        MLP branch : mlp_input(x, a1_sig) -> FFN            (MXU-bound)
        MHA branch : ln1(x) -> qkv -> paged KV gather -> wo (DMA-bound)

    This function forms the MLP input FIRST, so the FFN matmuls are never
    serialized behind the attention branch's block-table gather; on the
    paged C == 1 dense fast path both branches go down as ONE fused kernel
    dispatch (``attention.gqa_paged_dual`` ->
    ``kernels.ops.dual_branch_decode``) that overlaps page DMAs with FFN
    MXU work.  Off the fused-kernel path the arithmetic is op-for-op the
    sequential path's — same primitives, same operands, same residual-merge
    association — so logits match up to XLA fusing the two programs
    differently (a few float32 ulps; the fused TPU kernel's tiled
    accumulation is tolerance-close too); under explicit TP the two
    partial sums merge in the SAME single fused all-reduce as the
    sequential fused path (no extra collectives; asserted structurally in
    ``core.tp.make_tp_decode_step`` consumers).
    """
    axis = plan.tp_axis
    # MLP branch input — depends on (x, a1_sig) only; `a=None` is safe
    # because DUAL_BRANCH_MODES never read the block's own attention
    mlp_in = _mlp_input(cfg, p, x, None, a1_sig, False)
    C = x.shape[1]
    if (plan.phase is Phase.PAGED and kind == "dense" and not cfg.use_mla
            and tok_slot is None and C == 1 and cfg.attn_softcap == 0.0
            and isinstance(window, int) and window == 0):
        # single-token dense tick: fused dual-branch dispatch (padded
        # layout only — a packed buffer of length 1 is NOT a (B, 1) tick);
        # the one kernel runs both branches, so it counts under ``attn``
        with layer_scope("attn"):
            h = L.norm_apply(p["ln1"], x, cfg.norm)
            a, y, new_cache = A.gqa_paged_dual(p["attn"], p["ffn"], cfg, h,
                                               mlp_in, cache, block_tables,
                                               pos, n_valid,
                                               kernels=plan.kernels)
        aux = _no_aux()
    else:
        # the sequential path's attention ops (the packed kernel serves the
        # MHA branch of a packed tick; arithmetic matches op-for-op, so
        # tokens stay bit-identical)
        a, new_cache = _self_attention(p, cfg, x, None, window, plan,
                                       cache=cache, pos=pos,
                                       block_tables=block_tables,
                                       n_valid=n_valid, tok_slot=tok_slot,
                                       tok_pos=tok_pos)
        y, aux = _ffn_apply(p, cfg, mlp_in, kind, plan)
    if axis is not None:
        # one fused collective per block, same as the sequential fused path
        return (x + _assemble(a + y, axis, plan.grad_compress),
                a, aux, new_cache)
    # replicated: keep the sequential path's (x + a) + y association so
    # dual-branch logits differ from sequential ones only by XLA fusion
    return (x + a) + y, a, aux, new_cache


def _block_apply_sp(p, cfg, x_s, a1_sig, positions, window, *, kind,
                    is_block0, plan: ExecutionPlan):
    """Sequence-parallel (Megatron-SP) block inside the explicit-TP
    shard_map: ``x_s`` is the (B, S/tp, D) sequence shard of the residual
    stream; the output shard stays (B, S/tp, D).

    Collective structure per block (reduce ops map 1:1 onto the replicated
    path's all-reduces, at 1/tp the output bytes):

      fused (fal/parallel steady state):
          all-gather(x_s) -> attention + MLP partials over the full
          sequence -> ONE reduce-scatter(a + y) back to the shard.
      assembled (preln/falplus/ablations, post-norms):
          all-gather(x_s) -> attention partial -> reduce-scatter(a) ->
          sharded LN region forms mlp_input on the shard ->
          all-gather(mlp_input) -> MLP partial -> reduce-scatter(y).
      block 0 with a first-attention export (fal/falplus):
          the attention partial pays a true all-reduce instead of the
          reduce-scatter — the signal feeds EVERY later block at EVERY
          position, so it is the one tensor that must stay fully
          assembled and replicated (the paper's single extra collective,
          still paid exactly once for the whole depth).

    LayerNorms run per-token, so ln1/ln2/post-norms apply to sharded or
    gathered tensors interchangeably; the MLP/MoE kernels need the full
    sequence because their hidden/expert shards partial-sum over devices
    spanning ALL tokens.  MoE routing sees the identical gathered input on
    every device, so ``moe_apply_partial`` composes unchanged and the
    reduce-scatter completes the expert combine.
    """
    axis = plan.tp_axis
    shard = x_s.shape[1]

    def gather(v):
        return jax.lax.all_gather(v, axis, axis=1, tiled=True)

    def scatter(v):
        # plan.grad_compress routes the BACKWARD all-gather (the transpose
        # of this reduce-scatter) through the compressed exchange; 'none'
        # lowers the plain collective, byte-identical to before
        with layer_scope("assemble"):
            if plan.grad_compress != "none":
                return GC.compressed_psum_scatter(v, axis,
                                                  plan.grad_compress)
            return jax.lax.psum_scatter(v, axis, scatter_dimension=1,
                                        tiled=True)

    def local_slice(full):
        i = jax.lax.axis_index(axis)
        return jax.lax.dynamic_slice_in_dim(full, i * shard, shard, axis=1)

    x = gather(x_s)                                    # (B, S, D)
    a, _ = _self_attention(p, cfg, x, positions, window, plan)

    fused = not (cfg.post_norms
                 or fal.attention_must_assemble(cfg.connection, is_block0))
    if fused:
        mlp_in = _mlp_input(cfg, p, x, a, a1_sig, is_block0)
        y, aux = _ffn_apply(p, cfg, mlp_in, kind, plan)
        return x_s + scatter(a + y), a, aux, None

    full_export = is_block0 and cfg.connection in fal.USES_FIRST_ATTENTION
    if full_export:
        # block 0's signal export: fully assemble (and post-norm) the
        # attention so every device holds the replicated a1_raw
        a = _assemble(a, axis, plan.grad_compress)
        if cfg.post_norms:
            a = L.norm_apply(p["post_attn"], a, cfg.norm)
        resid_s = x_s + local_slice(a)
        mlp_in = _mlp_input(cfg, p, x, a, None, True)
    else:
        a_s = scatter(a)                               # complete, sharded
        if cfg.post_norms:
            a_s = L.norm_apply(p["post_attn"], a_s, cfg.norm)
        resid_s = x_s + a_s
        sig_s = local_slice(a1_sig) if a1_sig is not None else None
        mlp_in_s = _mlp_input(cfg, p, x_s, a_s, sig_s, is_block0)
        mlp_in = gather(mlp_in_s)                      # LN region -> full

    y, aux = _ffn_apply(p, cfg, mlp_in, kind, plan)
    y_s = scatter(y)
    if cfg.post_norms:
        y_s = L.norm_apply(p["post_ffn"], y_s, cfg.norm)
    return resid_s + y_s, a, aux, None


def window_schedule(cfg, n_layers=None):
    """Per-layer sliding windows.  gemma2: alternate local/global."""
    n = n_layers or cfg.n_layers
    if cfg.layer_pattern == "local_global" and cfg.sliding_window:
        return [cfg.sliding_window if i % 2 == 0 else 0 for i in range(n)]
    return [cfg.sliding_window] * n
