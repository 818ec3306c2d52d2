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

Decode and chunk kernels: the block table and per-request positions ride
in as scalar-prefetch operands (``PrefetchScalarGridSpec``); the K/V
BlockSpec index maps read the block table directly, so each grid step
DMAs one physical page of one KV head into VMEM — no gathered
(B, T*page) copy is ever materialised in HBM.  Grid: (B, Hkv, T) with T
sequential (TPU grids execute in order); the G query heads sharing a kv
head are processed together as a (G, D) tile — (C*G, D) for the chunked
kernel — so the page matmuls hit the MXU.  Online-softmax running
max/denominator/accumulator live in VMEM scratch, carried across the T
page steps; pages whose first slot is at/beyond the lane's live history
are skipped with ``pl.when``.

Packed kernel: the unit of work is a segment's query rows against a block
of that segment's pages, all KV heads at once.  The wrapper derives the
segment table on device from ``tok_slot``/``tok_pos`` (``_packed_units``):
a segment (a run of one slot's rows at consecutive positions) goes
through a query window of 8 or 32 rows if it fits one from its 8-aligned
first row (a decode token, a speculative proposal), else through windows
of up to 264 rows, so a prefill chunk reads each page once per window.
The kernel is one loop over the units' live page blocks: each step's
pages arrive by manual DMA straight from the pools in HBM, all heads of a
page in one copy, double-buffered so the next block's copies run while
this one is attended; the heads go through in batched matmuls.  Pages
past a unit's last position and padding rows cost neither a step nor a
DMA.  With 128-multiple pages the pools are read as (P, Hkv, D, page),
the layout a TPU keeps them in, so the kernel's operands are bitcasts of
the pools; narrower pages are copied to (P, Hkv, page, D).  When a head
group's query, output and softmax state outgrow ``_HEAD_GROUP_VMEM`` the
grid splits the KV heads into groups, each walking every unit.

Quantized KV pages: every paged kernel takes optional per-page-row
``k_scale``/``v_scale`` pools ((P, 1, page_size) fp32, shared across KV
heads; the unit middle dim makes the (1, 1, page) block span the pool's
full last two dims, as the TPU's (8, 128) tiling rule requires).  When
present, the page's scale row rides along with its K/V page (one extra
tiny DMA per page) and is applied along the key axis, where it already
lies in lanes: K's scale multiplies the (rows, page) scores and V's scale
the (rows, page) softmax weights before the PV matmul.  Narrow pages in,
fp32 softmax accumulate; with scales absent the lowered program is
unchanged.

Target: TPU.  Validated with ``interpret=True`` on CPU against
``repro.kernels.ref.paged_attention_ref`` /
``ref.paged_chunk_attention_ref`` / ``ref.paged_packed_attention_ref``.
"""
from __future__ import annotations

import functools
import math

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


#: query-row windows of the packed kernel, smallest first.  A segment goes
#: through the smallest window that holds it from its 8-aligned first row
#: (a decode token: 8 rows; a speculative proposal: 32); a longer one
#: through as many windows of the largest size as it spans, so a 256-token
#: prefill chunk at any alignment takes one window
_Q_WINDOWS = (8, 32, 264)
#: rows of a window start on a multiple of this (f32 sublanes)
_ROW_ALIGN = 8
#: VMEM for a head group's q and out blocks (double-buffered) and its
#: online-softmax state; the group is the most KV heads that fit
_HEAD_GROUP_VMEM = 40 << 20
#: VMEM for the double-buffered K/V page blocks; a block is the most pages
#: (of a head group) that fit
_KV_BLOCK_VMEM = 8 << 20
#: VMEM for one batch of heads' f32 scores over a page block
_SCORE_VMEM = 4 << 20
#: fields of the packed kernel's unit table (one flat int32 SMEM array)
_ROW0, _LEN, _WIN, _KIND, _SLOT, _POS0 = range(6)


def _lanes(n):
    return -(-n // 128) * 128


def _pad_lanes(x):
    """x with its last dim zero-padded to whole 128-lane tiles."""
    pad = _lanes(x.shape[-1]) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _packed_units(tok_slot, tok_pos, t_pad, windows, page, pages_per_block):
    """Segment table of a packed token buffer, cut into query windows.

    A segment is a maximal run of rows of one slot at consecutive
    positions (``pack_tokens`` lays each slot's tokens out as one).  It
    goes through the smallest window of ``windows`` that holds it from its
    8-aligned first row, else through windows of the largest size, the
    first starting at that aligned row and each next one where the last
    ended.  A window that would run past ``t_pad`` is moved back to end
    there.  Each such (segment, window) pair is a unit; padding rows
    (``tok_pos == -1``) belong to none.

    Returns ``(units, n_blocks)``: ``units`` (6*T,) int32, field-major
    (``_ROW0`` first row, ``_LEN`` rows, ``_WIN`` window start, ``_KIND``
    index into ``windows``, ``_SLOT``, ``_POS0`` first row's position),
    live units first; ``n_blocks`` (1,): page blocks over all units.
    """
    T = tok_pos.shape[0]
    idx = jnp.arange(T, dtype=jnp.int32)
    live = tok_pos >= 0
    false = jnp.zeros((1,), bool)
    cont = jnp.concatenate([false, live[1:] & live[:-1]
                            & (tok_slot[1:] == tok_slot[:-1])
                            & (tok_pos[1:] == tok_pos[:-1] + 1)])
    head = live & ~cont
    tail = live & ~jnp.concatenate([cont[1:], false])
    seg0 = jax.lax.cummax(jnp.where(head, idx, 0))
    seg_end = jax.lax.cummin(jnp.where(tail, idx + 1, T), reverse=True)
    aligned = seg0 // _ROW_ALIGN * _ROW_ALIGN
    big = len(windows) - 1
    kind = jnp.full((T,), big, jnp.int32)
    rows = jnp.full((T,), windows[big], jnp.int32)
    for k in reversed(range(big)):
        a = jnp.minimum(aligned, t_pad - windows[k])
        fits = seg_end - a <= windows[k]
        kind = jnp.where(fits, k, kind)
        rows = jnp.where(fits, windows[k], rows)
    a_big = jnp.minimum(aligned, t_pad - windows[big])
    unit_head = head | (live & (kind == big) & (idx > seg0)
                        & ((idx - a_big) % windows[big] == 0))
    win = jnp.minimum(jnp.where(head, aligned, idx), t_pad - rows)
    n = jnp.minimum(seg_end, win + rows) - idx
    # compact the unit heads to the front (rows of no unit drop out)
    at = jnp.where(unit_head, jnp.cumsum(unit_head) - 1, T)
    fields = jnp.zeros((6, T), jnp.int32).at[:, at].set(
        jnp.stack([idx, n, win, kind, tok_slot, tok_pos]), mode="drop")
    n_units = jnp.sum(unit_head, dtype=jnp.int32)
    pages = (fields[_POS0] + fields[_LEN] + page - 1) // page
    blocks = jnp.where(idx < n_units,
                       (pages + pages_per_block - 1) // pages_per_block, 0)
    return fields.reshape(-1), jnp.sum(blocks, dtype=jnp.int32)[None]


def _paged_packed_kernel(bt_ref, units_ref, nb_ref, q_ref, k_hbm, v_hbm,
                         *rest, scale, page, n_tb, windows, quantized,
                         keys_in_lanes):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem, m_scr, l_scr,
         acc_scr) = rest
    else:
        o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr = rest
    hb, G, _, D = q_ref.shape
    Dv = o_ref.shape[-1]
    bk = kbuf.shape[1]
    U = units_ref.shape[0] // 6
    h0 = pl.program_id(0) * hb
    n_items = nb_ref[0]

    def field(f, u):
        return units_ref[f * U + u]

    def n_pages(u):
        return (field(_POS0, u) + field(_LEN, u) + page - 1) // page

    def dma(u, b, slot, start):
        """Start (or wait for) the copies of unit u's page block b: every
        live page of it, all heads of the group, from the pools as they
        lie; pages past the unit's last position are not fetched."""
        s, npg = field(_SLOT, u), n_pages(u)
        for j in range(bk):
            @pl.when(b * bk + j < npg)
            def _(j=j):
                pid = bt_ref[s * n_tb + b * bk + j]
                cps = [pltpu.make_async_copy(k_hbm.at[pid, pl.ds(h0, hb)],
                                             kbuf.at[slot, j], sem.at[slot]),
                       pltpu.make_async_copy(v_hbm.at[pid, pl.ds(h0, hb)],
                                             vbuf.at[slot, j], sem.at[slot])]
                if quantized:
                    cps += [pltpu.make_async_copy(ks_hbm.at[pid],
                                                  ksbuf.at[slot, j],
                                                  sem.at[slot]),
                            pltpu.make_async_copy(vs_hbm.at[pid],
                                                  vsbuf.at[slot, j],
                                                  sem.at[slot])]
                for c in cps:
                    c.start() if start else c.wait()

    def attend(u, b, slot, R):
        """Online-softmax step of unit u (window of R rows) over its page
        block b, every head of the group; the last block writes the
        unit's rows of the output."""
        GR = G * R
        a = pl.multiple_of(field(_WIN, u), _ROW_ALIGN)
        row0, n = field(_ROW0, u), field(_LEN, u)
        first = b == 0
        last = (b + 1) * bk >= n_pages(u)
        # window row r sits at position pos0 + (a + r - row0); rows outside
        # the unit are computed and never stored.  The block's pages lie
        # side by side; pages past the unit's context hold earlier pages
        # or zeros and are masked like every key past a row's position
        r = jax.lax.broadcasted_iota(jnp.int32, (1, GR, 1), 1) % R
        q_pos = field(_POS0, u) - row0 + a + r
        k_pos = b * bk * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, bk * page), 2)
        visible = k_pos <= q_pos                       # (1, GR, bk*page)
        if quantized:
            ks = jnp.concatenate([ksbuf[slot, j][:, :page]
                                  for j in range(bk)], axis=1)
            vs = jnp.concatenate([vsbuf[slot, j][:, :page]
                                  for j in range(bk)], axis=1)
        # heads go through in batches whose scores fit _SCORE_VMEM: all of
        # the group for a decode window, fewer for a prefill window
        hc = max(d for d in range(1, hb + 1) if hb % d == 0
                 and d * GR * bk * page * 4 <= _SCORE_VMEM or d == 1)

        def block(buf, hs, d):
            """Heads hs of the block's pages in f32: (hc, d, bk*page), or
            (hc, bk*page, d) for narrow pages."""
            if keys_in_lanes:
                return jnp.concatenate([buf[slot, j, hs] for j in range(bk)],
                                       axis=2).astype(jnp.float32)
            return jnp.concatenate([buf[slot, j, hs][..., :d]
                                    for j in range(bk)],
                                   axis=1).astype(jnp.float32)

        def heads(c, carry):
            hs = pl.ds(pl.multiple_of(c * hc, hc), hc)
            q = q_ref[hs, :, pl.ds(a, R), :].reshape(hc, GR, D)
            m_prev = jnp.where(first, NEG_INF, m_scr[hs, :GR])
            l_prev = jnp.where(first, 0.0, l_scr[hs, :GR])
            acc_prev = jnp.where(first, 0.0, acc_scr[hs, :GR])
            s = jax.lax.dot_general(
                q, block(kbuf, hs, D),
                (((2,), (1 if keys_in_lanes else 2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * ks                             # (1, bk*page) rows
            s = jnp.where(visible, s, NEG_INF)         # (hc, GR, bk*page)
            m = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m)
            p = jnp.exp(s - m)
            l = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
            pv = p * vs if quantized else p
            acc = acc_prev * alpha + jax.lax.dot_general(
                pv, block(vbuf, hs, Dv),
                (((2,), (2 if keys_in_lanes else 1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)    # (hc, GR, Dv)

            @pl.when(jnp.logical_not(last))
            def _keep():
                m_scr[hs, :GR] = m
                l_scr[hs, :GR] = l
                acc_scr[hs, :GR] = acc

            @pl.when(last)
            def _emit():
                o = (acc / jnp.maximum(l, 1e-30)).reshape(hc, G, R, Dv)
                rows = a + jax.lax.broadcasted_iota(jnp.int32,
                                                    (1, 1, R, 1), 2)
                mine = (rows >= row0) & (rows < row0 + n)
                win = o_ref.at[hs, :, pl.ds(a, R), :]
                win[...] = jnp.where(mine, o, win[...])
            return carry

        jax.lax.fori_loop(0, hb // hc, heads, 0)

    @pl.when(pl.program_id(0) == 0)
    def _zero_buffers():
        # pages past a unit's context are never fetched, so their buffer
        # slots hold earlier pages or these zeros, never uninitialised bits
        # (masked weights are exact zeros, and 0 * NaN would not be)
        for buf in (kbuf, vbuf) + ((ksbuf, vsbuf) if quantized else ()):
            buf[...] = jnp.zeros_like(buf)

    o_ref[...] = jnp.zeros_like(o_ref)          # padding rows emit exact 0

    @pl.when(n_items > 0)
    def _prefetch_first():
        dma(0, 0, 0, True)

    def item(w, carry):
        u, b = carry
        slot = w % 2
        done = (b + 1) * bk >= n_pages(u)
        nu = jnp.where(done, u + 1, u)
        nb = jnp.where(done, 0, b + 1)

        @pl.when(w + 1 < n_items)
        def _prefetch_next():
            dma(nu, nb, 1 - slot, True)

        dma(u, b, slot, False)
        kind = field(_KIND, u)
        for k, R in enumerate(windows):
            pl.when(kind == k)(functools.partial(attend, u, b, slot, R))
        return nu, nb

    jax.lax.fori_loop(0, n_items, item, (jnp.int32(0), jnp.int32(0)))


def paged_packed_attention(q, k_pages, v_pages, block_tables, tok_slot,
                           tok_pos, *, scale=None, k_scale=None,
                           v_scale=None, interpret=False):
    """Packed ragged paged attention — the token-packed serving kernel.

    q: (T, H, D) — one flat buffer of query tokens where token t belongs
    to lane ``tok_slot[t]`` at logical position ``tok_pos[t]`` (its K/V
    already scattered into the pools); k_pages/v_pages: (P, page, Hkv, D*);
    block_tables: (S, Tb) int32 per-SLOT tables; tok_slot/tok_pos: (T,)
    int32.  Returns (T, H, Dv).

    The wrapper derives the segment table on device (``_packed_units``);
    the kernel walks its units' live page blocks in one loop, prefetching
    the next block while the current one is attended, with all KV heads of
    a group per step.  Token t sees the keys of its own slot's pages at
    positions <= ``tok_pos[t]``; padding tokens carry tok_pos == -1 and
    emit exactly 0 (same convention as the oracle).
    """
    T, H, D = q.shape
    page, Hkv = k_pages.shape[1], k_pages.shape[2]
    Dv = v_pages.shape[-1]
    G = H // Hkv
    Tb = block_tables.shape[1]
    scale = D ** -0.5 if scale is None else scale
    quantized = k_scale is not None
    t_pad = -(-T // _ROW_ALIGN) * _ROW_ALIGN
    windows = tuple(sorted({min(r, t_pad) for r in _Q_WINDOWS}))
    r_max = windows[-1]

    # head group: the most KV heads whose q/out blocks (double-buffered)
    # and online-softmax state fit _HEAD_GROUP_VMEM; block: the most pages
    # of the group whose double-buffered K and V fit _KV_BLOCK_VMEM
    def group_bytes(h):
        return 4 * h * G * (4 * t_pad * _lanes(max(D, Dv))
                            + r_max * (2 * 128 + _lanes(Dv)))
    hb = max([h for h in range(1, Hkv + 1) if Hkv % h == 0
              and group_bytes(h) <= _HEAD_GROUP_VMEM] or [1])

    # q upcast as the online softmax reads it, KV heads leading
    qt = q.astype(jnp.float32).reshape(T, Hkv, G, D).transpose(1, 2, 0, 3)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, t_pad - T), (0, 0)))
    # pages of 128-multiple rows: (P, Hkv, D, page), the layout a TPU keeps
    # such pools in, so these are bitcasts of the pools, not copies.  A
    # narrower page is copied to (P, Hkv, page, D), the head dim padded to
    # whole lanes as the kernel's DMAs need
    keys_in_lanes = page % 128 == 0
    if keys_in_lanes:
        kt = k_pages.transpose(0, 2, 3, 1)
        vt = v_pages.transpose(0, 2, 3, 1)
    else:
        kt = _pad_lanes(k_pages.transpose(0, 2, 1, 3))
        vt = _pad_lanes(v_pages.transpose(0, 2, 1, 3))

    page_bytes = hb * (math.prod(kt.shape[2:]) * kt.dtype.itemsize
                      + math.prod(vt.shape[2:]) * vt.dtype.itemsize)
    bk = max(1, min(Tb, _KV_BLOCK_VMEM // (2 * page_bytes)))
    vmem = group_bytes(hb) + 2 * bk * page_bytes + (16 << 20)
    units, n_blocks = _packed_units(tok_slot.astype(jnp.int32),
                                    tok_pos.astype(jnp.int32), t_pad,
                                    windows, page, bk)

    # the pools stay in HBM, where the kernel's copies read them: left to
    # XLA, a pool may be staged whole into VMEM by the op before (the
    # interpreter has no memory spaces)
    def in_hbm(x):
        return x if interpret else pltpu.with_memory_space_constraint(
            x, pltpu.HBM)

    in_specs = [pl.BlockSpec((hb, G, t_pad, D), lambda g, *_: (g, 0, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM)]
    operands = [qt, in_hbm(kt), in_hbm(vt)]
    scratch = [pltpu.VMEM((2, bk, hb) + kt.shape[2:], k_pages.dtype),
               pltpu.VMEM((2, bk, hb) + vt.shape[2:], v_pages.dtype)]
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pltpu.HBM)] * 2
        operands += [in_hbm(_pad_lanes(k_scale)),
                     in_hbm(_pad_lanes(v_scale))]
        scratch += [pltpu.VMEM((2, bk, 1, _lanes(page)), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hb, G * r_max, 1), jnp.float32),
                pltpu.VMEM((hb, G * r_max, 1), jnp.float32),
                pltpu.VMEM((hb, G * r_max, Dv), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Hkv // hb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((hb, G, t_pad, Dv),
                               lambda g, *_: (g, 0, 0, 0)),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(_paged_packed_kernel, scale=scale, page=page,
                               n_tb=Tb, windows=windows, quantized=quantized,
                               keys_in_lanes=keys_in_lanes)
    out = pl.pallas_call(
        kernel,
        name="paged_packed_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, G, t_pad, Dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(vmem, 100 << 20)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32).reshape(-1), units, n_blocks,
      *operands)
    return out[:, :, :T].transpose(2, 0, 1, 3).reshape(T, H, Dv) \
        .astype(q.dtype)


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
