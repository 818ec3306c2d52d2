"""Pallas TPU paged-attention kernels (GQA, block-table gather).

``paged_decode_attention`` — one query token per request attends to its KV
history stored in fixed-size pages scattered through
(num_pages, page_size, Hkv, D) pools.  ``paged_chunk_attention`` — the
C >= 1 generalisation of the padded (slots, C) layout: every lane carries
a C-token query chunk at its own position (per-lane ``pos`` / ``n_valid``
vectors), causal within the chunk.  ``paged_packed_attention`` — the
segment-aware kernel behind the serving engine's token-PACKED tick: one
flat (T,) token buffer with per-token ``(slot, pos)`` ids, so a
prefilling lane contributes up to ``chunk`` tokens and a decoding lane
exactly one in the SAME dispatch, and the tick's FLOPs scale with live
tokens instead of slots x chunk.

In both kernels the block table and per-request positions ride in as
scalar-prefetch operands (``PrefetchScalarGridSpec``): the K/V BlockSpec
index maps read the block table directly, so each grid step DMAs exactly
one physical page into VMEM — no gathered (B, T*page) copy is ever
materialised in HBM.

Quantized KV pages: every paged kernel takes optional per-page-row
``k_scale``/``v_scale`` pools ((P, 1, page_size) fp32, shared across KV
heads; the unit middle dim makes the (1, 1, page) block span the pool's
full last two dims, as the TPU's (8, 128) tiling rule requires).  When
present, the page's scale row rides the same block-table index map as its
K/V page (one extra tiny DMA per page step) and is applied along the key
axis, where it already lies in lanes: K's scale multiplies the (G, page)
scores and V's scale the (G, page) softmax weights before the PV matmul.
Narrow pages in, fp32 softmax accumulate; with scales absent the lowered
program is unchanged.

Grid: (B, Hkv, T) with T sequential (TPU grids execute in order); the G
query heads sharing a kv head are processed together as a (G, D) tile —
(C*G, D) for the chunked kernel — so the page matmuls hit the MXU.
Online-softmax running max/denominator/accumulator live in VMEM scratch,
carried across the T page steps; pages whose first slot is at/beyond the
lane's live history are skipped with ``pl.when``.

Target: TPU.  Validated with ``interpret=True`` on CPU against
``repro.kernels.ref.paged_attention_ref`` /
``ref.paged_chunk_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, *rest,
                  scale, page_size, quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = sl_ref[b]
    k_start = it * page_size          # logical position of this page's slot 0

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (page, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, page)
        if quantized:
            s = s * ks_ref[0]                             # (1, page) rows
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < seq_len, s, NEG_INF)

        m_prev = m_scr[...]                               # (G,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        m_scr[...] = m_cur
        v = v_ref[0, 0].astype(jnp.float32)               # (page, Dv)
        pv = p * vs_ref[0] if quantized else p
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # skip pages entirely past the request's history
    pl.when(k_start < seq_len)(_body)

    @pl.when(it == nt - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                           scale=None, k_scale=None, v_scale=None,
                           interpret=False):
    """q: (B, H, D); k_pages/v_pages: (P, page, Hkv, D*);
    block_tables: (B, T) int32; seq_lens: (B,) int32;
    k_scale/v_scale: optional (P, 1, page) fp32 dequant pools
    -> (B, H, Dv)."""
    B, H, D = q.shape
    page, Hkv = k_pages.shape[1], k_pages.shape[2]
    Dv = v_pages.shape[-1]
    G = H // Hkv
    T = block_tables.shape[1]
    scale = D ** -0.5 if scale is None else scale
    quantized = k_scale is not None

    qg = q.reshape(B, Hkv, G, D)
    kt = k_pages.transpose(0, 2, 1, 3)                # (P, Hkv, page, D)
    vt = v_pages.transpose(0, 2, 1, 3)

    in_specs = [
        pl.BlockSpec((1, 1, G, D),
                     lambda b, h, t, bt, sl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda b, h, t, bt, sl: (bt[b, t], h, 0, 0)),
        pl.BlockSpec((1, 1, page, Dv),
                     lambda b, h, t, bt, sl: (bt[b, t], h, 0, 0)),
    ]
    operands = [qg, kt, vt]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, page),
                                  lambda b, h, t, bt, sl:
                                  (bt[b, t], 0, 0))] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, h, t, bt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale, page_size=page,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      *operands)
    return out.reshape(B, H, Dv)


def _paged_chunk_kernel(bt_ref, pos_ref, nv_ref, q_ref, k_ref, v_ref, *rest,
                        scale, page_size, G, quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    seq_len = pos + nv_ref[b]         # this lane's live history (keys < it)
    k_start = it * page_size          # logical position of this page's slot 0

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # (C*G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (page, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (C*G, page)
        if quantized:
            s = s * ks_ref[0]                             # (1, page) rows
        # row r is chunk lane c = r // G at logical position pos + c: causal
        # within the chunk (k_pos <= q_pos) over live keys (k_pos < seq_len)
        q_pos = pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((k_pos <= q_pos) & (k_pos < seq_len), s, NEG_INF)

        m_prev = m_scr[...]                               # (C*G,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        # rows with no visible key yet keep m == NEG_INF, where s - m == 0
        # would count every masked key: zero those weights explicitly so the
        # no-visible-key rows emit 0 (the oracle's convention)
        p = jnp.where(m_cur[:, None] > NEG_INF / 2, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        m_scr[...] = m_cur
        v = v_ref[0, 0].astype(jnp.float32)               # (page, Dv)
        pv = p * vs_ref[0] if quantized else p
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # skip pages entirely past the lane's live history
    pl.when(k_start < seq_len)(_body)

    @pl.when(it == nt - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def paged_chunk_attention(q, k_pages, v_pages, block_tables, pos, n_valid, *,
                          scale=None, k_scale=None, v_scale=None,
                          interpret=False):
    """Chunked paged attention — per-lane rectangular (B, C) layout.

    Kept as the padded reference the packed serving kernel
    (``paged_packed_attention``) is benchmarked against.

    q: (B, C, H, D) — lane b's C query tokens at logical positions
    ``pos[b] .. pos[b] + C - 1``, first ``n_valid[b]`` valid (their K/V are
    already scattered into the pools); k_pages/v_pages: (P, page, Hkv, D*);
    block_tables: (B, T) int32; pos/n_valid: (B,) int32 -> (B, C, H, Dv).
    Causal within the chunk.  Rows past ``n_valid`` are finite but
    MEANINGLESS (they attend whatever live history the lane has; rows with
    no visible key emit 0) — callers must only read each lane's first
    ``n_valid`` rows; the serving engine gathers the last valid one.
    """
    B, C, H, D = q.shape
    page, Hkv = k_pages.shape[1], k_pages.shape[2]
    Dv = v_pages.shape[-1]
    G = H // Hkv
    T = block_tables.shape[1]
    scale = D ** -0.5 if scale is None else scale
    quantized = k_scale is not None

    # (B, C, Hkv, G, D) -> (B, Hkv, C*G, D): one MXU tile per (lane, kv head)
    qg = q.reshape(B, C, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hkv, C * G, D)
    kt = k_pages.transpose(0, 2, 1, 3)                # (P, Hkv, page, D)
    vt = v_pages.transpose(0, 2, 1, 3)

    in_specs = [
        pl.BlockSpec((1, 1, C * G, D),
                     lambda b, h, t, bt, ps, nv: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda b, h, t, bt, ps, nv: (bt[b, t], h, 0, 0)),
        pl.BlockSpec((1, 1, page, Dv),
                     lambda b, h, t, bt, ps, nv: (bt[b, t], h, 0, 0)),
    ]
    operands = [qg, kt, vt]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, page),
                                  lambda b, h, t, bt, ps, nv:
                                  (bt[b, t], 0, 0))] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, C * G, Dv),
                               lambda b, h, t, bt, ps, nv: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * G,), jnp.float32),
            pltpu.VMEM((C * G,), jnp.float32),
            pltpu.VMEM((C * G, Dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_chunk_kernel, scale=scale,
                               page_size=page, G=G, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        name="paged_chunk_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, C * G, Dv), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
      n_valid.astype(jnp.int32), *operands)
    return out.reshape(B, Hkv, C, G, Dv).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, Dv)


def _paged_packed_kernel(bt_ref, sl_ref, ps_ref, q_ref, k_ref, v_ref, *rest,
                         scale, page_size, quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    t = pl.program_id(0)
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = ps_ref[t]                 # -1 for padding tokens (nothing visible)
    k_start = it * page_size          # logical position of this page's slot 0

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (page, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, page)
        if quantized:
            s = s * ks_ref[0]                             # (1, page) rows
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_scr[...]                               # (G,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        m_scr[...] = m_cur
        v = v_ref[0, 0].astype(jnp.float32)               # (page, Dv)
        pv = p * vs_ref[0] if quantized else p
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # skip pages entirely past this token's position; padding tokens
    # (q_pos == -1) skip every page, so l stays 0 and the row emits 0
    pl.when(k_start <= q_pos)(_body)

    @pl.when(it == nt - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def paged_packed_attention(q, k_pages, v_pages, block_tables, tok_slot,
                           tok_pos, *, scale=None, k_scale=None,
                           v_scale=None, interpret=False):
    """Packed ragged paged attention — the token-packed serving kernel.

    q: (T, H, D) — one flat buffer of query tokens where token t belongs
    to lane ``tok_slot[t]`` at logical position ``tok_pos[t]`` (its K/V
    already scattered into the pools); k_pages/v_pages: (P, page, Hkv, D*);
    block_tables: (S, Tb) int32 per-SLOT tables; tok_slot/tok_pos: (T,)
    int32.  Returns (T, H, Dv).

    Grid (T, Hkv, Tb): the K/V BlockSpec index maps read the block table
    through the scalar-prefetched per-token slot ids
    (``bt[tok_slot[t], j]``), so each grid step DMAs exactly one physical
    page of the token's OWN segment — the per-token generalisation of
    ``paged_decode_attention``'s per-lane indirection.  Pages past a
    token's position are skipped; padding tokens carry tok_pos == -1 and
    emit exactly 0 (same convention as the oracle).
    """
    T, H, D = q.shape
    page, Hkv = k_pages.shape[1], k_pages.shape[2]
    Dv = v_pages.shape[-1]
    G = H // Hkv
    Tb = block_tables.shape[1]
    scale = D ** -0.5 if scale is None else scale
    quantized = k_scale is not None

    qg = q.reshape(T, Hkv, G, D)
    kt = k_pages.transpose(0, 2, 1, 3)                # (P, Hkv, page, D)
    vt = v_pages.transpose(0, 2, 1, 3)

    in_specs = [
        pl.BlockSpec((1, 1, G, D),
                     lambda t, h, j, bt, sl, ps: (t, h, 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda t, h, j, bt, sl, ps: (bt[sl[t], j], h, 0, 0)),
        pl.BlockSpec((1, 1, page, Dv),
                     lambda t, h, j, bt, sl, ps: (bt[sl[t], j], h, 0, 0)),
    ]
    operands = [qg, kt, vt]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, page),
                                  lambda t, h, j, bt, sl, ps:
                                  (bt[sl[t], j], 0, 0))] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T, Hkv, Tb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda t, h, j, bt, sl, ps: (t, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_packed_kernel, scale=scale,
                               page_size=page, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        name="paged_packed_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), tok_slot.astype(jnp.int32),
      tok_pos.astype(jnp.int32), *operands)
    return out.reshape(T, H, Dv)


def _page_copy_kernel(src_ref, dst_ref, x_ref, o_ref):
    del src_ref, dst_ref
    o_ref[...] = x_ref[...]


def page_copy(pool, src, dst, *, interpret=False):
    """Copy-on-write page duplication inside one KV pool: the full rows of
    pages ``src`` (n,) are copied over pages ``dst`` (n,) in place.

    pool: (P, page, ...) — any paged pool layout (K, V, MLA latent, ...);
    src/dst: (n,) int32 physical page ids.  ``dst`` pages must be distinct
    freshly-allocated targets; ``src`` pages may repeat.  Returns the pool
    with the n page rows rewritten — the pool buffer is aliased into the
    output (``input_output_aliases``), so pages outside ``dst`` are
    untouched bytes, not recomputed copies.

    Grid (n,): ``src``/``dst`` ride in as scalar-prefetch operands and the
    in/out BlockSpec index maps address page ``src[i]`` / ``dst[i]``
    directly, so each grid step is exactly one page-row DMA through VMEM —
    the device-side memcpy behind ``BlockTable`` copy-on-write.
    """
    P, page = pool.shape[0], pool.shape[1]
    tail = 1
    for d in pool.shape[2:]:
        tail *= d
    flat = pool.reshape(P, page, tail)
    n = src.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, page, tail), lambda i, s, d: (s[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, page, tail), lambda i, s, d: (d[i], 0, 0)),
    )
    out = pl.pallas_call(
        _page_copy_kernel,
        name="page_copy",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(src.astype(jnp.int32), dst.astype(jnp.int32), flat)
    return out.reshape(pool.shape)
