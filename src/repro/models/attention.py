"""Attention: GQA (+qk_norm, sliding window, logit softcap), MLA (DeepSeek),
blockwise flash-style jnp implementation, and KV-cache decode paths.

The blockwise implementation is the dry-run/compile path (Pallas kernels do
not lower on the CPU host backend); the Pallas TPU kernel in
``repro.kernels.flash_attention`` implements the same online-softmax algorithm
and is validated against ``ref.py`` in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.obs.trace import layer_scope


# ------------------------------------------------------------------------- #
# core blockwise attention (training / prefill)
# ------------------------------------------------------------------------- #
def blockwise_attention(q, k, v, *, causal=True, window=0, cap=0.0,
                        block_q=512, scale=None, q_offset=0):
    """Flash-style attention, scanning over query blocks.

    q: (B, Sq, H, Dh)   k: (B, Sk, Hkv, Dh)   v: (B, Sk, Hkv, Dv)
    Memory: O(block_q * Sk) scores instead of O(Sq * Sk).
    ``q_offset``: position of q[0] within the key sequence (cross-attention /
    chunked prefill).
    """
    B, Sq, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    Dv = v.shape[-1]
    scale = Dh ** -0.5 if scale is None else scale

    bq = min(block_q, Sq)
    pad = (-Sq) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = q.shape[1] // bq
    qb = q.reshape(B, nblk, bq, Hkv, G, Dh).transpose(1, 0, 2, 3, 4, 5)
    k_pos = jnp.arange(Sk)

    def one_block(i, qblk):
        # qblk: (B, bq, Hkv, G, Dh)
        q_pos = q_offset + i * bq + jnp.arange(bq)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, k,
                       preferred_element_type=jnp.float32) * scale
        s = L.softcap(s, cap)
        mask = jnp.ones((bq, Sk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if not (isinstance(window, int) and window == 0):
            # ``window`` may be a traced per-layer scalar (scan over layers);
            # window <= 0 disables the mask.
            w = jnp.asarray(window)
            mask &= (k_pos[None, :] > q_pos[:, None] - w) | (w <= 0)
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
        return o  # (B, bq, Hkv, G, Dv)

    out = jax.lax.map(lambda args: one_block(*args),
                      (jnp.arange(nblk), qb))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nblk * bq, H, Dv)
    return out[:, :Sq]


def decode_attention(q, k_cache, v_cache, pos, *, window=0, cap=0.0,
                     scale=None):
    """One-token attention against a KV cache.

    q: (B, 1, H, Dh); caches: (B, S, Hkv, D*); pos: (B,) int32 index of the
    current token (keys at indices <= pos are valid).
    """
    B, _, H, Dh = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = Dh ** -0.5 if scale is None else scale
    qg = q.reshape(B, Hkv, G, Dh)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = L.softcap(s, cap)
    k_pos = jnp.arange(S)[None]                        # (1, S)
    mask = k_pos <= pos[:, None]
    if not (isinstance(window, int) and window == 0):
        w = jnp.asarray(window)
        mask &= (k_pos > (pos[:, None] - w)) | (w <= 0)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, 1, H, v_cache.shape[-1])


# ------------------------------------------------------------------------- #
# paged KV cache (serving engine)
# ------------------------------------------------------------------------- #
# The serving engine stores KV state in fixed-size pages: a pool shaped
# (num_pages, page_size, ...) plus a per-request block table mapping logical
# block j (positions j*page_size .. (j+1)*page_size - 1) to a physical page.
# Page 0 is a scratch page owned by no request: masked lanes of padded
# prefill chunks are redirected there, so ragged batches never corrupt live
# pages.  Writes are idempotent per (request, position) — re-decoding the
# same position overwrites the same slot (the engine relies on this for
# preemption -> resume).


def paged_scatter(pages, vals, block_tables, pos, n_valid, page_size):
    """Write a (B, C, ...) chunk of per-token values into the page pool.

    pages: (P, page_size, ...); vals: (B, C, ...); block_tables: (B, T);
    pos: (B,) logical position of each request's first chunk token;
    n_valid: (B,) number of valid tokens in the chunk (rest -> scratch page).
    Runs under the ``kv_write`` layer scope.
    """
    B, C = vals.shape[:2]
    T = block_tables.shape[1]
    with layer_scope("kv_write"):
        lpos = pos[:, None] + jnp.arange(C)[None]                 # (B, C)
        blk = jnp.clip(lpos // page_size, 0, T - 1)
        pg = jnp.take_along_axis(block_tables, blk, axis=1)       # (B, C)
        valid = jnp.arange(C)[None] < n_valid[:, None]
        pg = jnp.where(valid, pg, 0)                              # scratch
        flat_idx = (pg * page_size + lpos % page_size).reshape(-1)
        flat = pages.reshape((pages.shape[0] * page_size,)
                             + pages.shape[2:])
        flat = flat.at[flat_idx].set(
            vals.reshape((B * C,) + vals.shape[2:]).astype(pages.dtype))
        return flat.reshape(pages.shape)


def packed_scatter(pages, vals, block_tables, tok_slot, tok_pos, page_size):
    """Write a flat (T, ...) packed token buffer into the page pool.

    pages: (P, page_size, ...); vals: (T, ...) one value per packed token;
    block_tables: (S, Tb) per-SLOT tables; tok_slot/tok_pos: (T,) — token t
    belongs to lane ``tok_slot[t]`` at logical position ``tok_pos[t]``.
    Padding tokens carry tok_pos == -1 and are redirected to the scratch
    page (page 0), so ragged packs never corrupt live pages.  Runs under
    the ``kv_write`` scope, like ``paged_scatter``.
    """
    Tb = block_tables.shape[1]
    with layer_scope("kv_write"):
        pos = jnp.maximum(tok_pos, 0)
        blk = jnp.clip(pos // page_size, 0, Tb - 1)
        pg = block_tables[tok_slot, blk]                          # (T,)
        pg = jnp.where(tok_pos >= 0, pg, 0)                       # scratch
        flat_idx = pg * page_size + pos % page_size
        flat = pages.reshape((pages.shape[0] * page_size,)
                             + pages.shape[2:])
        flat = flat.at[flat_idx].set(vals.astype(pages.dtype))
        return flat.reshape(pages.shape)


def paged_gather(pages, block_tables):
    """(P, page_size, ...) x (B, T) -> (B, T*page_size, ...): the request's
    logical KV sequence (gathered index == logical position)."""
    g = pages[block_tables]
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def _gather_dequant(pages, scale_pages, block_tables):
    """Gather a K/V pool to (B, Sk, Hkv, D) and, when a per-page-row scale
    pool rides along (quantized KV), dequantize to fp32 — the masked-gather
    fallback's mirror of the kernels' in-VMEM dequant."""
    g = paged_gather(pages, block_tables)
    if scale_pages is None:
        return g
    s = paged_gather(scale_pages[:, 0], block_tables)             # (B, Sk)
    return g.astype(jnp.float32) * s[..., None, None]


def chunk_attention(q, k, v, q_pos, *, window=0, cap=0.0, scale=None):
    """Multi-token attention against a gathered cache with per-request
    positions (chunked prefill / paged decode).

    q: (B, C, H, Dh); k, v: (B, Sk, Hkv, D*); q_pos: (B, C) global position
    of each query token.  Key at gathered index j is visible iff j <= q_pos.
    """
    B, C, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = Dh ** -0.5 if scale is None else scale
    qg = q.reshape(B, C, Hkv, G, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = L.softcap(s, cap)
    k_pos = jnp.arange(Sk)[None, None]                            # (1,1,Sk)
    mask = k_pos <= q_pos[:, :, None]
    if not (isinstance(window, int) and window == 0):
        w = jnp.asarray(window)
        mask &= (k_pos > q_pos[:, :, None] - w) | (w <= 0)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(B, C, H, v.shape[-1])


#: quantized KV page storage dtypes (``EngineConfig.kv_dtype`` values);
#: "" keeps the engine's ``cache_dtype`` pools (bit-preserved legacy path)
KV_DTYPES = ("", "bf16", "int8", "fp8")
_KV_STORAGE = {"bf16": "bfloat16", "int8": "int8", "fp8": "float8_e4m3fn"}

#: accuracy contract of int8 pages: the logits of a tick over int8 pages
#: lie within INT8_LOGIT_RTOL * max|logit| of the same tick over
#: unquantized pages (the per-row amax/127 grid moves each cached K/V
#: entry by at most 0.4% of its row's amax)
INT8_LOGIT_RTOL = 0.05


def _kv_qmax(pages_dtype) -> float:
    return 448.0 if jnp.dtype(pages_dtype).name.startswith("float8") else 127.0


def _quant_rows(vals, pages_dtype):
    """Per-token-row KV quantization: vals (..., Hkv, Dh) -> (q, scale)
    with ``scale = amax / qmax`` reduced over (Hkv, Dh) — ONE fp32 scale
    per cached token row, shared across KV heads.  History-free by
    construction (a row's scale depends only on that row's values), so
    re-scattering a position is idempotent and a COW'd page is
    bit-identical to its source — the properties the prefix-cache and
    spec-rollback identity tests pin.  ``q`` is returned in fp32 units of
    the narrow grid (int grids pre-rounded and clipped); the page
    scatter's ``astype(pages.dtype)`` performs the final cast."""
    qmax = _kv_qmax(pages_dtype)
    a = jnp.max(jnp.abs(vals.astype(jnp.float32)), axis=(-2, -1))
    scale = jnp.maximum(a / qmax, 1e-8)
    q = vals.astype(jnp.float32) / scale[..., None, None]
    if not jnp.dtype(pages_dtype).name.startswith("float8"):
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    return q, scale


def gqa_init_paged_cache(cfg, num_pages, page_size, dtype, kv_dtype=""):
    """K/V page pools (P, page, Hkv, Dh).  ``kv_dtype`` selects the
    quantized page format: "" stores in ``dtype`` (the engine's
    ``cache_dtype`` — existing path, bit-preserved); "bf16" stores
    bfloat16 with no scales; "int8"/"fp8" store the narrow dtype plus
    per-page-row (P, 1, page) fp32 ``k_scale``/``v_scale`` pools shared
    across KV heads — 2 bytes/elt -> 1 byte/elt + 4 bytes/row."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, "
                         f"got {kv_dtype!r}")
    Hkv, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    store = jnp.dtype(_KV_STORAGE.get(kv_dtype, dtype))
    cache = {
        "k": jnp.zeros((num_pages, page_size, Hkv, Dh), store),
        "v": jnp.zeros((num_pages, page_size, Hkv, Dh), store),
    }
    if kv_dtype in ("int8", "fp8"):
        # (P, 1, page): the kernels' (1, 1, page) scale blocks then span
        # the pool's full last two dims (TPU tiling rule)
        cache["k_scale"] = jnp.ones((num_pages, 1, page_size), jnp.float32)
        cache["v_scale"] = jnp.ones((num_pages, 1, page_size), jnp.float32)
    return cache


def _gqa_paged_qkv_scatter(p, cfg, x, cache, block_tables, pos, n_valid):
    """Shared prologue of the sequential and dual-branch paged paths:
    project q/k/v at the chunk's positions and scatter k/v into the page
    pools (quantizing per token row first when the cache carries
    ``k_scale``/``v_scale`` pools).  Returns (q, new_cache, positions) —
    ONE implementation so the two paths cannot drift apart (they are
    asserted bit-identical)."""
    C = x.shape[1]
    page = cache["k"].shape[1]
    positions = pos[:, None] + jnp.arange(C)[None]
    q, k, v = gqa_qkv(p, cfg, x, positions)
    nc = {}
    if "k_scale" in cache:
        k, ks = _quant_rows(k, cache["k"].dtype)
        v, vs = _quant_rows(v, cache["v"].dtype)
        nc["k_scale"] = paged_scatter(cache["k_scale"][:, 0], ks,
                                      block_tables, pos, n_valid,
                                      page)[:, None]
        nc["v_scale"] = paged_scatter(cache["v_scale"][:, 0], vs,
                                      block_tables, pos, n_valid,
                                      page)[:, None]
    nc["k"] = paged_scatter(cache["k"], k, block_tables, pos, n_valid, page)
    nc["v"] = paged_scatter(cache["v"], v, block_tables, pos, n_valid, page)
    return q, nc, positions


def gqa_paged_apply(p, cfg, x, cache, block_tables, pos, n_valid, *,
                    window=0, kernels="auto"):
    """Chunked decode/prefill against a paged cache.  x: (B, C, D) with C >= 1
    (C == 1 is a decode-only tick; C > 1 serves lanes at ANY phase — per-lane
    ``pos``/``n_valid`` let prefilling lanes advance up to C positions while
    decoding lanes advance 1 in the same dispatch).  ``kernels`` is the
    plan's kernel path (``ExecutionPlan.kernels``).  Returns
    (out (B,C,D), new_cache)."""
    B, C = x.shape[:2]
    q, nc, positions = _gqa_paged_qkv_scatter(p, cfg, x, cache,
                                              block_tables, pos, n_valid)
    kc, vc = nc["k"], nc["v"]
    ks, vs = nc.get("k_scale"), nc.get("v_scale")
    if cfg.attn_softcap == 0.0 and isinstance(window, int) and window == 0:
        # full-attention tick: the block-table kernel paths (Pallas on TPU,
        # gather-based ref on CPU) — the TPU kernels DMA pages directly so
        # no gathered (B, T*page) copy is ever materialised in HBM; scale
        # pools (quantized KV) ride the same block tables and dequantize
        # inside the kernel's VMEM load
        from repro.kernels import ops
        kw = ops.kernel_args(kernels)
        if C == 1:
            o = ops.paged_decode_attention(q[:, 0], kc, vc, block_tables,
                                           pos + 1, k_scale=ks,
                                           v_scale=vs, **kw)[:, None]
        else:
            o = ops.paged_chunk_attention(q, kc, vc, block_tables, pos,
                                          n_valid, k_scale=ks, v_scale=vs,
                                          **kw)
    else:
        # sliding-window / softcapped layers (gemma2): masked gather path
        o = chunk_attention(q, _gather_dequant(kc, ks, block_tables),
                            _gather_dequant(vc, vs, block_tables), positions,
                            window=window, cap=cfg.attn_softcap)
        if ks is not None:
            o = o.astype(x.dtype)
    return o.reshape(B, C, -1) @ p["wo"].astype(x.dtype), nc


def gqa_paged_dual(p, ffn, cfg, x, mlp_in, cache, block_tables, pos,
                   n_valid, *, kernels="auto"):
    """Dual-branch single-token paged tick: the block-table attention gather
    and the dense FFN matmuls go down as ONE fused dispatch
    (``kernels.ops.dual_branch_decode``) so the TPU overlaps page DMAs with
    FFN MXU work; the CPU fallback runs exactly the sequential path's ops
    (gather-free ref attention + ``layers.mlp_apply``), keeping dual-branch
    logits within a few float32 ulps of sequential decode.

    x: (B, 1, D) post-ln1 attention input; mlp_in: (B, 1, D) the block's
    MLP input (independent of this block's attention — the FAL property).
    Returns (attn_out (B,1,D), ffn_out (B,1,D), new_cache).
    """
    B, C = x.shape[:2]
    q, nc, _ = _gqa_paged_qkv_scatter(p, cfg, x, cache, block_tables,
                                      pos, n_valid)
    from repro.kernels import ops
    kw = ops.kernel_args(kernels)
    if "k_scale" in nc:
        # quantized KV: the fused dual-branch kernel has no dequant path,
        # so issue the two branches as independent ops (XLA still overlaps
        # them) — the scale-aware paged kernel + the dense MLP
        o = ops.paged_decode_attention(q[:, 0], nc["k"], nc["v"],
                                       block_tables, pos + 1,
                                       k_scale=nc["k_scale"],
                                       v_scale=nc["v_scale"], **kw)
        y = L.mlp_apply(ffn, mlp_in, cfg.mlp)
    else:
        o, y = ops.dual_branch_decode(q[:, 0], nc["k"], nc["v"],
                                      block_tables, pos + 1, mlp_in, ffn,
                                      kind=cfg.mlp, **kw)
    a = o[:, None].reshape(B, C, -1) @ p["wo"].astype(x.dtype)
    return a, y, nc


def gqa_packed_apply(p, cfg, x, cache, block_tables, tok_slot, tok_pos, *,
                     window=0, kernels="auto"):
    """Token-packed ragged tick against a paged cache.  x: (1, T, D) — one
    flat buffer of packed tokens where token t belongs to lane
    ``tok_slot[t]`` at logical position ``tok_pos[t]`` (padding tokens at
    tok_pos == -1 scatter to scratch and yield meaningless rows that
    callers must not read).  A prefilling lane contributes up to ``chunk``
    contiguous tokens, a decoding lane exactly one, in the SAME dispatch —
    FLOPs scale with live tokens, not slots x chunk.  Returns
    (out (1,T,D), new_cache)."""
    B, T = x.shape[:2]
    page = cache["k"].shape[1]
    positions = jnp.maximum(tok_pos, 0)[None]                     # (1, T)
    q, k, v = gqa_qkv(p, cfg, x, positions)                       # (1,T,H,Dh)
    k, v = k[0], v[0]
    nc = {}
    if "k_scale" in cache:
        k, ks_rows = _quant_rows(k, cache["k"].dtype)
        v, vs_rows = _quant_rows(v, cache["v"].dtype)
        nc["k_scale"] = packed_scatter(cache["k_scale"][:, 0], ks_rows,
                                       block_tables, tok_slot, tok_pos,
                                       page)[:, None]
        nc["v_scale"] = packed_scatter(cache["v_scale"][:, 0], vs_rows,
                                       block_tables, tok_slot, tok_pos,
                                       page)[:, None]
    nc["k"] = packed_scatter(cache["k"], k, block_tables, tok_slot, tok_pos,
                             page)
    nc["v"] = packed_scatter(cache["v"], v, block_tables, tok_slot, tok_pos,
                             page)
    kc, vc = nc["k"], nc["v"]
    ks, vs = nc.get("k_scale"), nc.get("v_scale")
    if cfg.attn_softcap == 0.0 and isinstance(window, int) and window == 0:
        # full-attention tick: the segment-aware block-table kernel (Pallas
        # on TPU DMAs each token's OWN pages; gather-based ref on CPU);
        # quantized pages dequantize in-kernel via the scale pools
        from repro.kernels import ops
        o = ops.paged_packed_attention(q[0], kc, vc, block_tables,
                                       tok_slot, tok_pos, k_scale=ks,
                                       v_scale=vs,
                                       **ops.kernel_args(kernels))[None]
    else:
        # sliding-window / softcapped layers (gemma2): per-token masked
        # gather — each token indexes its own slot's gathered sequence
        kg = _gather_dequant(kc, ks, block_tables)[tok_slot]      # (T,Sk,..)
        vg = _gather_dequant(vc, vs, block_tables)[tok_slot]
        o = chunk_attention(q[0][:, None], kg, vg, tok_pos[:, None],
                            window=window, cap=cfg.attn_softcap)[:, 0][None]
        if ks is not None:
            o = o.astype(x.dtype)
    return o.reshape(B, T, -1) @ p["wo"].astype(x.dtype), nc


# ------------------------------------------------------------------------- #
# GQA module
# ------------------------------------------------------------------------- #
def gqa_init(key, cfg, cross=False):
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": L.dense_init(ks[0], d, H * Dh, cfg.param_dtype)["w"],
        "wk": L.dense_init(ks[1], d, Hkv * Dh, cfg.param_dtype)["w"],
        "wv": L.dense_init(ks[2], d, Hkv * Dh, cfg.param_dtype)["w"],
        "wo": L.dense_init(ks[3], H * Dh, d, cfg.param_dtype,
                           scale=1.0 / np.sqrt(H * Dh * 2 * cfg.n_layers))["w"],
    }
    if cfg.qk_norm:
        p["qnorm"] = L.norm_init(Dh, "rmsnorm", cfg.param_dtype)
        p["knorm"] = L.norm_init(Dh, "rmsnorm", cfg.param_dtype)
    return p


def gqa_qkv(p, cfg, x, positions, kv_x=None, rope=True):
    """Project to q,k,v (with qk_norm + rope).

    Head counts are derived from the WEIGHT shapes, not cfg: inside the
    explicit-TP shard_map (model.decoder_stack_tp) each device holds a
    head-aligned column slice of wq/wk/wv, so the same code is the local
    kernel over H/tp heads — and, with the row-sharded ``wo`` downstream,
    yields the per-device partial sum of the paper's Fig 2."""
    Dh = cfg.resolved_head_dim
    H, Hkv = p["wq"].shape[-1] // Dh, p["wk"].shape[-1] // Dh
    B, S = x.shape[:2]
    kv_x = x if kv_x is None else kv_x
    Skv = kv_x.shape[1]
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, Dh)
    k = (kv_x @ p["wk"].astype(x.dtype)).reshape(B, Skv, Hkv, Dh)
    v = (kv_x @ p["wv"].astype(x.dtype)).reshape(B, Skv, Hkv, Dh)
    if cfg.qk_norm:
        q = L.norm_apply(p["qnorm"], q)
        k = L.norm_apply(p["knorm"], k)
    if cfg.rope and rope and positions is not None:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def sequence_parallel_attention(q, k, v, cfg, plan, *, causal=True,
                                window=0):
    """Context-parallel attention (beyond-paper, EXPERIMENTS.md §Perf P1).

    q is sharded on the sequence dim over the ``model`` axis; K/V are
    gathered (jit inserts the all-gather at the shard_map boundary).  Each
    shard runs blockwise attention over its local q rows with the correct
    global ``q_offset`` for causal/window masks.  Removes the score-matmul
    all-reduces GSPMD emits when kv-heads don't divide the model axis.
    """
    from jax.sharding import PartitionSpec as P
    mesh, dax, max_ = plan.mesh, plan.data_axes, plan.model_axis
    M = mesh.shape[max_]
    S = q.shape[1]
    assert S % M == 0, (S, M)

    def local(q_loc, k_full, v_full, w):
        off = jax.lax.axis_index(max_) * (S // M)
        return blockwise_attention(
            q_loc, k_full, v_full, causal=causal, window=w,
            cap=cfg.attn_softcap, block_q=min(cfg.attn_block_q, S // M),
            q_offset=off)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(dax, max_, None, None),
                                 P(dax, None, None, None),
                                 P(dax, None, None, None), P()),
                       out_specs=P(dax, max_, None, None),
                       check_vma=False)
    # window may be a traced per-layer scalar (scan xs) — pass explicitly
    return fn(q, k, v, jnp.asarray(window, jnp.int32))


def _use_seq_parallel(cfg, plan, S):
    """Context-parallel attention opt-in (cfg.attn_shard == 'sequence');
    distinct from plan.sequence_parallel (Megatron-SP LN regions, which
    runs inside the explicit-TP shard_map where plan.mesh is None)."""
    if cfg.attn_shard != "sequence" or plan is None or plan.mesh is None:
        return False
    return S % plan.mesh.shape[plan.model_axis] == 0


def _kv_group_slice(k, v, cfg, plan):
    """Megatron GQA fallback for n_kv_heads < tp_size inside the explicit-TP
    shard_map: wk/wv arrive REPLICATED (launch.mesh kv_replicated specs),
    every device computes all KV heads cheaply and slices the one its query
    heads attend to (tp_size/n_kv_heads devices share each KV head)."""
    if plan is None or plan.tp_axis is None:
        return k, v
    tp = plan.tp_size
    if cfg.n_kv_heads % tp == 0:
        return k, v          # kv heads are sharded like query heads
    rep = tp // cfg.n_kv_heads
    idx = jax.lax.axis_index(plan.tp_axis) // rep
    return (jax.lax.dynamic_slice_in_dim(k, idx, 1, axis=2),
            jax.lax.dynamic_slice_in_dim(v, idx, 1, axis=2))


def gqa_apply(p, cfg, x, positions, *, window=0, causal=True, plan=None):
    """Full-sequence attention (train / prefill). Returns (B,S,D) — a TP
    partial sum when the weights are the explicit-TP shards."""
    q, k, v = gqa_qkv(p, cfg, x, positions)
    k, v = _kv_group_slice(k, v, cfg, plan)
    B, S = x.shape[:2]
    if _use_seq_parallel(cfg, plan, S):
        o = sequence_parallel_attention(q, k, v, cfg, plan, causal=causal,
                                        window=window)
    else:
        o = blockwise_attention(q, k, v, causal=causal, window=window,
                                cap=cfg.attn_softcap, block_q=cfg.attn_block_q)
    return o.reshape(B, S, -1) @ p["wo"].astype(x.dtype)


def gqa_cross_apply(p, cfg, x, enc_out):
    """Cross-attention (whisper decoder): no causal mask, no rope."""
    q, k, v = gqa_qkv(p, cfg, x, None, kv_x=enc_out, rope=False)
    o = blockwise_attention(q, k, v, causal=False, cap=0.0,
                            block_q=cfg.attn_block_q)
    B, S = x.shape[:2]
    return o.reshape(B, S, -1) @ p["wo"].astype(x.dtype)


def gqa_init_cache(cfg, batch, seq, dtype):
    Hkv, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, seq, Hkv, Dh), jnp.dtype(dtype)),
        "v": jnp.zeros((batch, seq, Hkv, Dh), jnp.dtype(dtype)),
    }


def gqa_decode(p, cfg, x, cache, pos, *, window=0):
    """One-step decode. x: (B,1,D); pos: (B,) current position. Returns
    (out, new_cache)."""
    B = x.shape[0]
    q, k, v = gqa_qkv(p, cfg, x, pos[:, None].astype(jnp.int32))
    upd = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0))
    kc = upd(cache["k"], k.astype(cache["k"].dtype), pos)
    vc = upd(cache["v"], v.astype(cache["v"].dtype), pos)
    o = decode_attention(q, kc, vc, pos, window=window, cap=cfg.attn_softcap)
    out = o.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return out, {"k": kc, "v": vc}


# ------------------------------------------------------------------------- #
# MLA (DeepSeek-V3 Multi-head Latent Attention)
# ------------------------------------------------------------------------- #
def mla_init(key, cfg):
    d, H = cfg.d_model, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    if rq:
        q = {"w_dq": L.dense_init(ks[0], d, rq, cfg.param_dtype)["w"],
             "q_norm": L.norm_init(rq, "rmsnorm", cfg.param_dtype),
             "w_uq": L.dense_init(ks[1], rq, H * (dn + dr),
                                  cfg.param_dtype)["w"]}
    else:   # no q LoRA: one direct projection
        q = {"w_q": L.dense_init(ks[0], d, H * (dn + dr),
                                 cfg.param_dtype)["w"]}
    p = {
        **q,
        "w_dkv": L.dense_init(ks[2], d, rkv, cfg.param_dtype)["w"],
        "kv_norm": L.norm_init(rkv, "rmsnorm", cfg.param_dtype),
        "w_kr": L.dense_init(ks[3], d, dr, cfg.param_dtype)["w"],
        "w_uk": L.dense_init(ks[4], rkv, H * dn, cfg.param_dtype)["w"],
        "w_uv": L.dense_init(ks[5], rkv, H * dv, cfg.param_dtype)["w"],
        "wo": L.dense_init(ks[6], H * dv, d, cfg.param_dtype,
                           scale=1.0 / np.sqrt(H * dv * 2 * cfg.n_layers))["w"],
    }
    return p


def _mla_q(p, cfg, x, positions):
    B, S = x.shape[:2]
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    # head count from the weight shape: w_uq / w_q may be a column (head)
    # shard inside the explicit-TP shard_map (same contract as gqa_qkv)
    if "w_q" in p:
        q = x @ p["w_q"].astype(x.dtype)
    else:
        cq = L.norm_apply(p["q_norm"], x @ p["w_dq"].astype(x.dtype))
        q = cq @ p["w_uq"].astype(x.dtype)
    H = q.shape[-1] // (dn + dr)
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, cfg, x, positions):
    c = L.norm_apply(p["kv_norm"], x @ p["w_dkv"].astype(x.dtype))
    kr = x @ p["w_kr"].astype(x.dtype)                       # (B,S,dr), shared head
    kr = L.apply_rope(kr[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c, kr


def mla_apply(p, cfg, x, positions, plan=None):
    """Full-sequence MLA (train / prefill): expand k,v; blockwise attention.

    Like gqa_apply, head count comes from the (possibly head-sharded)
    up-projection weights; with the row-sharded ``wo`` the result is then a
    TP partial sum."""
    B, S = x.shape[:2]
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    H = p["w_uk"].shape[-1] // dn
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c, kr = _mla_ckv(p, cfg, x, positions)
    k_nope = (c @ p["w_uk"].astype(x.dtype)).reshape(B, S, H, dn)
    v = (c @ p["w_uv"].astype(x.dtype)).reshape(B, S, H, dv)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                                  (B, S, H, dr))], -1)
    scale = (dn + dr) ** -0.5
    if _use_seq_parallel(cfg, plan, S):
        # note: v head dim != qk head dim is fine (shard_map is shape-blind)
        o = sequence_parallel_attention(q, k, v, cfg, plan, causal=True)
    else:
        o = blockwise_attention(q, k, v, causal=True, scale=scale,
                                block_q=cfg.attn_block_q)
    return o.reshape(B, S, H * dv) @ p["wo"].astype(x.dtype)


def mla_init_cache(cfg, batch, seq, dtype):
    return {
        "c": jnp.zeros((batch, seq, cfg.kv_lora_rank), jnp.dtype(dtype)),
        "kr": jnp.zeros((batch, seq, cfg.qk_rope_head_dim), jnp.dtype(dtype)),
    }


def mla_decode(p, cfg, x, cache, pos):
    """Absorbed-matrix MLA decode: attention in the compressed latent space.
    Cache holds only (c, k_rope) per token — the reason long_500k is feasible.
    """
    B = x.shape[0]
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rkv = cfg.kv_lora_rank
    positions = pos[:, None].astype(jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)            # (B,1,H,dn/dr)
    c, kr = _mla_ckv(p, cfg, x, positions)                   # (B,1,rkv), (B,1,dr)
    upd = jax.vmap(lambda cc, u, i: jax.lax.dynamic_update_slice_in_dim(cc, u, i, 0))
    cc = upd(cache["c"], c.astype(cache["c"].dtype), pos)
    krc = upd(cache["kr"], kr.astype(cache["kr"].dtype), pos)
    # absorb W_uk into q:  q_lat[h] = q_nope[h] @ W_uk[:, h, :].T
    w_uk = p["w_uk"].astype(x.dtype).reshape(rkv, H, dn)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)   # (B,H,rkv)
    s = jnp.einsum("bhr,bkr->bhk", q_lat, cc,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bhd,bkd->bhk", q_rope[:, 0], krc,
                    preferred_element_type=jnp.float32)
    s *= (dn + dr) ** -0.5
    mask = jnp.arange(cc.shape[1])[None] <= pos[:, None]
    s = jnp.where(mask[:, None], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhk,bkr->bhr", pattn.astype(cc.dtype), cc)  # (B,H,rkv)
    w_uv = p["w_uv"].astype(x.dtype).reshape(rkv, H, dv)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv).reshape(B, 1, H * dv)
    return o @ p["wo"].astype(x.dtype), {"c": cc, "kr": krc}


def mla_init_paged_cache(cfg, num_pages, page_size, dtype):
    return {
        "c": jnp.zeros((num_pages, page_size, cfg.kv_lora_rank),
                       jnp.dtype(dtype)),
        "kr": jnp.zeros((num_pages, page_size, cfg.qk_rope_head_dim),
                        jnp.dtype(dtype)),
    }


def mla_paged_apply(p, cfg, x, cache, block_tables, pos, n_valid):
    """Chunked absorbed-matrix MLA decode against paged (c, k_rope) pages.
    x: (B, C, D); returns (out (B,C,D), new_cache)."""
    B, C = x.shape[:2]
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rkv = cfg.kv_lora_rank
    page = cache["c"].shape[1]
    positions = pos[:, None] + jnp.arange(C)[None]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)            # (B,C,H,dn/dr)
    c, kr = _mla_ckv(p, cfg, x, positions)                   # (B,C,rkv/dr)
    c_pool = paged_scatter(cache["c"], c, block_tables, pos, n_valid, page)
    kr_pool = paged_scatter(cache["kr"], kr, block_tables, pos, n_valid, page)
    cc = paged_gather(c_pool, block_tables)                  # (B,Sk,rkv)
    krc = paged_gather(kr_pool, block_tables)                # (B,Sk,dr)
    w_uk = p["w_uk"].astype(x.dtype).reshape(rkv, H, dn)
    q_lat = jnp.einsum("bchd,rhd->bchr", q_nope, w_uk)       # (B,C,H,rkv)
    s = jnp.einsum("bchr,bkr->bhck", q_lat, cc,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bchd,bkd->bhck", q_rope, krc,
                    preferred_element_type=jnp.float32)
    s *= (dn + dr) ** -0.5
    mask = jnp.arange(cc.shape[1])[None, None] <= positions[:, :, None]
    s = jnp.where(mask[:, None], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhck,bkr->bchr", pattn.astype(cc.dtype), cc)
    w_uv = p["w_uv"].astype(x.dtype).reshape(rkv, H, dv)
    o = jnp.einsum("bchr,rhd->bchd", o_lat, w_uv).reshape(B, C, H * dv)
    return o @ p["wo"].astype(x.dtype), {"c": c_pool, "kr": kr_pool}


def mla_packed_apply(p, cfg, x, cache, block_tables, tok_slot, tok_pos):
    """Token-packed absorbed-matrix MLA against paged (c, k_rope) pages.
    x: (1, T, D) packed buffer (see ``gqa_packed_apply`` for the token/
    segment contract); each token attends its OWN slot's gathered latent
    sequence.  Returns (out (1,T,D), new_cache)."""
    B, T = x.shape[:2]
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rkv = cfg.kv_lora_rank
    page = cache["c"].shape[1]
    positions = jnp.maximum(tok_pos, 0)[None]                     # (1, T)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)                 # (1,T,H,*)
    c, kr = _mla_ckv(p, cfg, x, positions)                        # (1,T,*)
    c_pool = packed_scatter(cache["c"], c[0], block_tables, tok_slot,
                            tok_pos, page)
    kr_pool = packed_scatter(cache["kr"], kr[0], block_tables, tok_slot,
                             tok_pos, page)
    cc = paged_gather(c_pool, block_tables)[tok_slot]             # (T,Sk,rkv)
    krc = paged_gather(kr_pool, block_tables)[tok_slot]           # (T,Sk,dr)
    w_uk = p["w_uk"].astype(x.dtype).reshape(rkv, H, dn)
    q_lat = jnp.einsum("thd,rhd->thr", q_nope[0], w_uk)           # (T,H,rkv)
    s = jnp.einsum("thr,tkr->thk", q_lat, cc,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("thd,tkd->thk", q_rope[0], krc,
                    preferred_element_type=jnp.float32)
    s *= (dn + dr) ** -0.5
    mask = jnp.arange(cc.shape[1])[None] <= tok_pos[:, None]      # (T, Sk)
    s = jnp.where(mask[:, None], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("thk,tkr->thr", pattn.astype(cc.dtype), cc)
    w_uv = p["w_uv"].astype(x.dtype).reshape(rkv, H, dv)
    o = jnp.einsum("thr,rhd->thd", o_lat, w_uv).reshape(B, T, H * dv)
    return o @ p["wo"].astype(x.dtype), {"c": c_pool, "kr": kr_pool}
