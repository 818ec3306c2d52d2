"""Pallas TPU flash attention (causal, GQA) — online-softmax with explicit
BlockSpec VMEM tiling.

TPU adaptation of the paper's FlashAttention dependency (§6.3): the grid's
minor-most dimension iterates KV blocks sequentially (TPU grids execute in
order), with the running max/denominator/accumulator in VMEM scratch.  Block
shapes are MXU-aligned (multiples of 128 on the lane dimension).

Target: TPU.  Validated with ``interpret=True`` on CPU against
``repro.kernels.ref.attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, block_q, block_k, seq_k):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_k
        if causal:
            mask &= k_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                              # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        m_scr[...] = m_cur
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # skip fully-masked KV blocks (block start beyond the last q row)
        pl.when(k_start <= q_start + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    scale=None, interpret=False):
    """q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D) -> (B, Sq, H, D).

    GQA handled by mapping query head h to kv head h // (H // Hkv) in the
    K/V BlockSpec index maps (no materialised broadcast).
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = D ** -0.5 if scale is None else scale
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)

    q_pad = (-Sq) % block_q
    k_pad = (-Sk) % block_k
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_k

    # layout (B, H, S, D) so the block tiles the last two dims
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, nq, nk)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, seq_k=Sk)
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    return out[:, :Sq]
