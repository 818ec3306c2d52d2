"""Pallas TPU fused dual-branch decode kernel: paged MHA gather || dense FFN.

Under the FAL-family connections (``core.fal.DUAL_BRANCH_MODES``) a decode
block's MLP input is independent of the block's own attention output, so the
attention branch (DMA-bound block-table page gather) and the FFN branch
(MXU-bound matmuls) can execute concurrently.  A single XLA program cannot
promise that overlap — this kernel enforces it: ONE ``pallas_call`` whose
grid interleaves the paged-attention page steps with FFN hidden-dim tiles,
so the DMA of page t+1 prefetches while the MXU runs FFN tile t's matmuls.

Grid: (B, Hkv, T), sequential on TPU.  The attention half is exactly the
``paged_attention`` online-softmax kernel (block table + seq_lens ride in as
scalar prefetch; each step DMAs one physical page).  The FFN half splits the
hidden dim F into ``Hkv * T`` column tiles of wi/wg (and matching row tiles
of wo); step (h, t) accumulates tile ``h*T + t``'s contribution to the FFN
output row in fp32 VMEM scratch.  Emission: attention out at the last page
step of each (b, h); FFN out at the last (h, t) step of each b.

Requires F % (Hkv * T) == 0; the TPU compiler further needs the tile
width F/(Hkv*T) to be a multiple of 128 (lane width), so the
``kernels.ops.dual_branch_decode`` dispatcher selects this kernel only then
and issues separate attention + FFN calls otherwise (still
dependency-free, just not fused).  The FFN row operand and output are
(B, 1, Dm) so each (1, 1, Dm) row block spans the full last two dims.

Target: TPU.  Validated with ``interpret=True`` on CPU against
``ref.paged_attention_ref`` + ``layers.mlp_apply`` in ``tests/test_dual_branch.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dual_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, x_ref, *ffn_refs,
                 scale, page_size, kind):
    if kind in ("swiglu", "geglu"):
        wi_ref, wg_ref, wo_ref, o_ref, f_ref, m_scr, l_scr, acc_scr, \
            ffn_scr = ffn_refs
    else:
        wi_ref, wo_ref, o_ref, f_ref, m_scr, l_scr, acc_scr, \
            ffn_scr = ffn_refs
        wg_ref = None
    b = pl.program_id(0)
    ih = pl.program_id(1)
    it = pl.program_id(2)
    nh = pl.num_programs(1)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init_attn():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((ih == 0) & (it == 0))
    def _init_ffn():
        ffn_scr[...] = jnp.zeros_like(ffn_scr)

    # ---- FFN branch: one hidden-dim tile per grid step (MXU) -------------
    xr = x_ref[0].astype(jnp.float32)                     # (1, Dm)
    hi = jax.lax.dot_general(
        xr, wi_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (1, Ft)
    if kind == "swiglu":
        hg = jax.lax.dot_general(
            xr, wg_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        hpart = jax.nn.silu(hg) * hi
    elif kind == "geglu":
        hg = jax.lax.dot_general(
            xr, wg_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        hpart = jax.nn.gelu(hg) * hi
    else:  # gelu
        hpart = jax.nn.gelu(hi)
    ffn_scr[...] += jax.lax.dot_general(
        hpart, wo_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (1, Dm)

    # ---- attention branch: one physical page per grid step (DMA + VPU) ---
    seq_len = sl_ref[b]
    k_start = it * page_size

    def _attn_body():
        q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (page, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, page)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < seq_len, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        m_scr[...] = m_cur
        v = v_ref[0, 0].astype(jnp.float32)               # (page, Dv)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    pl.when(k_start < seq_len)(_attn_body)

    @pl.when(it == nt - 1)
    def _emit_attn():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)

    @pl.when((ih == nh - 1) & (it == nt - 1))
    def _emit_ffn():
        f_ref[0] = ffn_scr[...].astype(f_ref.dtype)


def fused_dual_branch_decode(q, k_pages, v_pages, block_tables, seq_lens,
                             x, ffn, *, kind="swiglu", scale=None,
                             interpret=False):
    """q: (B, H, D); k_pages/v_pages: (P, page, Hkv, D*); block_tables:
    (B, T) int32; seq_lens: (B,) int32; x: (B, 1, Dm) FFN input rows; ffn:
    {"wi" (Dm, F) [, "wg" (Dm, F)], "wo" (F, Dm)}.
    Returns (attn (B, H, Dv), ffn_out (B, 1, Dm))."""
    B, H, D = q.shape
    page, Hkv = k_pages.shape[1], k_pages.shape[2]
    Dv = v_pages.shape[-1]
    G = H // Hkv
    T = block_tables.shape[1]
    Dm = x.shape[-1]
    F = ffn["wi"].shape[-1]
    n_tiles = Hkv * T
    if F % n_tiles:
        raise ValueError(f"fused dual-branch: d_ff={F} must divide into "
                         f"Hkv*T={n_tiles} tiles (dispatcher should have "
                         f"fallen back)")
    Ft = F // n_tiles
    scale = D ** -0.5 if scale is None else scale
    gated = kind in ("swiglu", "geglu")

    qg = q.reshape(B, Hkv, G, D)
    kt = k_pages.transpose(0, 2, 1, 3)                # (P, Hkv, page, D)
    vt = v_pages.transpose(0, 2, 1, 3)

    # FFN tile index for grid step (b, h, t): j = h*T + t
    in_specs = [
        pl.BlockSpec((1, 1, G, D), lambda b, h, t, bt, sl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda b, h, t, bt, sl: (bt[b, t], h, 0, 0)),
        pl.BlockSpec((1, 1, page, Dv),
                     lambda b, h, t, bt, sl: (bt[b, t], h, 0, 0)),
        pl.BlockSpec((1, 1, Dm), lambda b, h, t, bt, sl: (b, 0, 0)),
        pl.BlockSpec((Dm, Ft), lambda b, h, t, bt, sl: (0, h * T + t)),
    ]
    operands = [qg, kt, vt, x, ffn["wi"]]
    if gated:
        in_specs.append(
            pl.BlockSpec((Dm, Ft), lambda b, h, t, bt, sl: (0, h * T + t)))
        operands.append(ffn["wg"])
    in_specs.append(
        pl.BlockSpec((Ft, Dm), lambda b, h, t, bt, sl: (h * T + t, 0)))
    operands.append(ffn["wo"])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, T),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, G, Dv), lambda b, h, t, bt, sl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Dm), lambda b, h, t, bt, sl: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
            pltpu.VMEM((1, Dm), jnp.float32),
        ],
    )
    kernel = functools.partial(_dual_kernel, scale=scale, page_size=page,
                               kind=kind)
    out, ffn_out = pl.pallas_call(
        kernel,
        name="fused_dual_branch_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, 1, Dm), x.dtype)],
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), *operands)
    return out.reshape(B, H, Dv), ffn_out
