"""Pure-jnp oracles for the Pallas kernels (used by tests + interpret-mode
validation sweeps).

The paged oracles take optional per-page-row ``k_scale``/``v_scale`` pools
((P, 1, page_size) fp32, shared across KV heads — the quantized-KV page
format): when present, gathered K/V rows are dequantized as
``row.astype(f32) * scale`` right where the unquantized path upcasts, so
the fp32 softmax math downstream is IDENTICAL and the only difference is
the storage rounding."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dequant(rows, scale_pages, block_tables):
    """rows: gathered K/V (..., Sk, Hkv, D) already fp32; scale_pages:
    (P, 1, page_size) fp32 per-row scales or None; block_tables matches
    the gather that produced ``rows``.  Returns rows * scale (broadcast
    over heads and head dim)."""
    if scale_pages is None:
        return rows
    s = scale_pages[:, 0][block_tables]           # (..., T, page)
    s = s.reshape(s.shape[:-2] + (-1,))           # (..., Sk)
    return rows * s[..., None, None].astype(jnp.float32)


def attention_ref(q, k, v, *, causal=True, scale=None):
    """Naive attention oracle.  q: (B,Sq,H,D); k,v: (B,Sk,Hkv,D*)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens, *,
                        scale=None, k_scale=None, v_scale=None):
    """Paged-KV decode attention oracle (gather-based).

    q: (B, H, D) one query token per request;
    k_pages/v_pages: (P, page_size, Hkv, D*) pools;
    block_tables: (B, T) int32 logical-block -> physical-page;
    seq_lens: (B,) valid keys per request (gathered index < seq_len);
    k_scale/v_scale: optional (P, 1, page_size) fp32 dequant scale pools.
    Returns (B, H, Dv).
    """
    B, H, D = q.shape
    Hkv = k_pages.shape[2]
    G = H // Hkv
    scale = D ** -0.5 if scale is None else scale
    k = k_pages[block_tables]                     # (B, T, page, Hkv, D)
    k = _dequant(k.reshape(B, -1, Hkv, D).astype(jnp.float32),
                 k_scale, block_tables)
    v = _dequant(v_pages[block_tables].reshape(
        B, -1, Hkv, v_pages.shape[-1]).astype(jnp.float32),
        v_scale, block_tables)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(k.shape[1])[None] < seq_lens[:, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, v.shape[-1]).astype(q.dtype)


def paged_chunk_attention_ref(q, k_pages, v_pages, block_tables, pos,
                              n_valid, *, scale=None, k_scale=None,
                              v_scale=None):
    """Chunked paged-attention oracle (gather-based): C >= 1 query tokens per
    lane against block-table pages, causal within the chunk.

    q: (B, C, H, D) — lane b's queries sit at logical positions
    ``pos[b] .. pos[b] + C - 1``, of which the first ``n_valid[b]`` are
    valid (the chunk's own K/V have already been scattered into the pools);
    k_pages/v_pages: (P, page_size, Hkv, D*);  block_tables: (B, T) int32;
    pos/n_valid: (B,) int32.  Returns (B, C, H, Dv).

    A key at gathered index j is visible to chunk lane c iff
    ``j <= pos + c`` (causality, incl. within the chunk) and
    ``j < pos + n_valid`` (this lane's live history).  Rows past
    ``n_valid`` are finite but MEANINGLESS — they attend the lane's live
    history under the same mask, and rows with no visible key return 0 —
    the identical convention to the Pallas kernel, so the two agree on
    every row; callers must only read the first ``n_valid`` rows.
    """
    B, C, H, D = q.shape
    Hkv = k_pages.shape[2]
    G = H // Hkv
    Dv = v_pages.shape[-1]
    scale = D ** -0.5 if scale is None else scale
    k = _dequant(k_pages[block_tables].reshape(
        B, -1, Hkv, D).astype(jnp.float32), k_scale, block_tables)
    v = _dequant(v_pages[block_tables].reshape(
        B, -1, Hkv, Dv).astype(jnp.float32), v_scale, block_tables)
    qg = q.reshape(B, C, Hkv, G, D)
    s = jnp.einsum("bchgd,bkhd->bhgck", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    k_pos = jnp.arange(k.shape[1])[None, None]                  # (1, 1, Sk)
    q_pos = pos[:, None] + jnp.arange(C)[None]                  # (B, C)
    seq_len = (pos + n_valid)[:, None, None]
    mask = (k_pos <= q_pos[:, :, None]) & (k_pos < seq_len)     # (B, C, Sk)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, -1)[:, None, None, :, None], p, 0.0)
    o = jnp.einsum("bhgck,bkhd->bchgd", p, v.astype(jnp.float32))
    return o.reshape(B, C, H, Dv).astype(q.dtype)


def paged_packed_attention_ref(q, k_pages, v_pages, block_tables, tok_slot,
                               tok_pos, *, scale=None, k_scale=None,
                               v_scale=None):
    """Packed ragged paged-attention oracle (gather-based): a flat (T,)
    token buffer where token t belongs to lane ``tok_slot[t]`` at logical
    position ``tok_pos[t]`` — the segment-aware generalisation of
    ``paged_chunk_attention_ref`` that backs the token-packed tick.

    q: (T, H, D) packed query tokens; k_pages/v_pages: (P, page_size,
    Hkv, D*); block_tables: (S, Tb) int32 per-SLOT tables; tok_slot /
    tok_pos: (T,) int32.  Returns (T, H, Dv).

    Token t sees exactly the keys of its own slot's block table at
    gathered index j <= tok_pos[t] (causality; its own K/V and every
    earlier token of its segment are already scattered into the pools).
    Padding tokens carry tok_pos == -1: no key is visible and the row
    returns 0 — the identical convention to the Pallas kernel, so the two
    agree on every row; callers must only read live (tok_pos >= 0) rows.

    This is also the speculative-decode VERIFY oracle: a decode lane
    proposing n tokens packs them as one segment at positions
    pos..pos+n-1, and the per-token causal mask scores proposal j against
    exactly the context [0, pos+j] — so every row's attention equals what
    a sequential one-token-per-tick decode would have computed at that
    position.  K/V scattered for later-REJECTED proposals sit at
    positions beyond the lane's rewound ``pos``; ``k_pos <= tok_pos``
    keeps them invisible until the position is re-fed, at which point the
    scatter overwrites them before any read.
    """
    T, H, D = q.shape
    Hkv = k_pages.shape[2]
    G = H // Hkv
    Dv = v_pages.shape[-1]
    scale = D ** -0.5 if scale is None else scale
    bt = block_tables[tok_slot]                    # (T, Tb) per-token tables
    k = _dequant(k_pages[bt].reshape(
        T, -1, Hkv, D).astype(jnp.float32), k_scale, bt)   # (T, Sk, Hkv, D)
    v = _dequant(v_pages[bt].reshape(
        T, -1, Hkv, Dv).astype(jnp.float32), v_scale, bt)
    qg = q.reshape(T, Hkv, G, D)
    s = jnp.einsum("thgd,tkhd->thgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    k_pos = jnp.arange(k.shape[1])[None]                        # (1, Sk)
    mask = k_pos <= tok_pos[:, None]                            # (T, Sk)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, -1)[:, None, None, None], p, 0.0)
    o = jnp.einsum("thgk,tkhd->thgd", p, v.astype(jnp.float32))
    return o.reshape(T, H, Dv).astype(q.dtype)


def copy_pages_ref(pool, src, dst):
    """Oracle for the COW page copy: pool (P, page, ...) with the full rows
    at pages ``src`` (n,) written over the rows at pages ``dst`` (n,).
    ``dst`` indices must be distinct (each COW target is a freshly
    allocated page); ``src`` pages are read-only and may repeat."""
    return pool.at[dst].set(pool[src])


def ln_add_ref(x, a1n, scale, bias=None, *, kind="rmsnorm", eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    else:
        var = jnp.mean(jnp.square(xf), -1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return (y + a1n.astype(jnp.float32)).astype(x.dtype)
