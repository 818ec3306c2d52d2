"""Pallas kernel validation: shape/dtype sweeps in interpret mode vs the
pure-jnp oracles in repro.kernels.ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as FA
from repro.kernels import fused_ln_add as FL
from repro.kernels import ops
from repro.kernels import paged_attention as PA
from repro.kernels import ref as R


@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 192, 4, 1, 32),      # MQA, non-pow2 seq
    (2, 64, 4, 4, 128),      # wide head
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, Hkv, D, causal):
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = FA.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                             interpret=True)
    ref = R.attention_ref(q, k, v, causal=causal)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_dtypes(dtype):
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64)).astype(dt)
    k = jax.random.normal(ks[1], (2, 128, 2, 64)).astype(dt)
    v = jax.random.normal(ks[2], (2, 128, 2, 64)).astype(dt)
    out = FA.flash_attention(q, k, v, interpret=True)
    ref = R.attention_ref(q, k, v)
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert jnp.max(jnp.abs(out.astype(jnp.float32)
                           - ref.astype(jnp.float32))) < tol


def test_flash_attention_blockq_invariance():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 4, 64))
    v = jax.random.normal(ks[2], (1, 256, 4, 64))
    outs = [FA.flash_attention(q, k, v, block_q=bq, block_k=bk,
                               interpret=True)
            for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]]
    for o in outs[1:]:
        assert jnp.max(jnp.abs(o - outs[0])) < 1e-5


@pytest.mark.parametrize("shape", [(4, 96, 128), (2, 33, 256), (1, 7, 64)])
@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_ln_add_sweep(shape, kind, dtype):
    dt = jnp.dtype(dtype)
    d = shape[-1]
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], shape).astype(dt)
    a = jax.random.normal(ks[1], shape).astype(dt)
    sc = jax.random.normal(ks[2], (d,))
    bi = jax.random.normal(ks[3], (d,))
    out = FL.fused_ln_add(x, a, sc, bi, kind=kind, block_rows=32,
                          interpret=True)
    ref = R.ln_add_ref(x, a, sc, bi, kind=kind)
    tol = 2e-5 if dtype == "float32" else 5e-2
    assert jnp.max(jnp.abs(out.astype(jnp.float32)
                           - ref.astype(jnp.float32))) < tol


@pytest.mark.parametrize("B,H,Hkv,D,page,T", [
    (2, 4, 4, 32, 8, 4),     # MHA
    (2, 8, 2, 64, 16, 3),    # GQA 4:1
    (1, 4, 1, 32, 8, 5),     # MQA
])
def test_paged_attention_sweep(B, H, Hkv, D, page, T):
    P = T * B + 2
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (B, H, D))
    k_pages = jax.random.normal(ks[1], (P, page, Hkv, D))
    v_pages = jax.random.normal(ks[2], (P, page, Hkv, D))
    # distinct pages per request, ragged seq_lens incl. a page-boundary case
    bt = jnp.asarray(np.arange(1, 1 + B * T).reshape(B, T), jnp.int32)
    lens = [(T - 1) * page + 3, page, 1][:B]
    sl = jnp.asarray(lens + [5] * (B - len(lens)), jnp.int32)
    out = PA.paged_decode_attention(q, k_pages, v_pages, bt, sl,
                                    interpret=True)
    ref = R.paged_attention_ref(q, k_pages, v_pages, bt, sl)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


@pytest.mark.parametrize("C", [1, 4, 16])          # decode / small / chunk
def test_paged_chunk_attention_sweep(C):
    """Chunked (mixed-tick) kernel vs its gather oracle: ragged per-lane
    lengths (a prefilling lane, a decoding lane, an idle lane) at positions
    that straddle page boundaries."""
    B, H, Hkv, D, page, T = 3, 8, 2, 32, 8, 6
    P = T * B + 2
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (B, C, H, D))
    k_pages = jax.random.normal(ks[1], (P, page, Hkv, D))
    v_pages = jax.random.normal(ks[2], (P, page, Hkv, D))
    bt = jnp.asarray(np.arange(1, 1 + B * T).reshape(B, T), jnp.int32)
    # lane 0: full prefill chunk straddling a page boundary; lane 1: decode
    # lane (one valid token) mid-page; lane 2: empty lane with no history
    pos = jnp.asarray([page - 3, 2 * page + 5, 0], jnp.int32)
    nv = jnp.asarray([C, 1, 0], jnp.int32)
    out = PA.paged_chunk_attention(q, k_pages, v_pages, bt, pos, nv,
                                   interpret=True)
    ref = R.paged_chunk_attention_ref(q, k_pages, v_pages, bt, pos, nv)
    assert out.shape == (B, C, H, D)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5
    assert bool(jnp.all(out[2] == 0))            # idle lane emits zeros


def test_paged_chunk_attention_c1_matches_decode_kernel():
    """At C == 1 / n_valid == 1 the chunked kernel must agree with the
    single-token decode kernel contract (seq_lens == pos + 1)."""
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    B, H, Hkv, D, page, T = 2, 4, 2, 32, 8, 3
    q = jax.random.normal(ks[0], (B, 1, H, D))
    kp = jax.random.normal(ks[1], (B * T + 2, page, Hkv, D))
    vp = jax.random.normal(ks[2], (B * T + 2, page, Hkv, D))
    bt = jnp.asarray(np.arange(1, 1 + B * T).reshape(B, T), jnp.int32)
    pos = jnp.asarray([10, page - 1], jnp.int32)
    one = jnp.ones((B,), jnp.int32)
    chunk = PA.paged_chunk_attention(q, kp, vp, bt, pos, one, interpret=True)
    dec = PA.paged_decode_attention(q[:, 0], kp, vp, bt, pos + 1,
                                    interpret=True)
    assert jnp.max(jnp.abs(chunk[:, 0] - dec)) < 2e-5


#: packed-kernel sweep cases: (H, Hkv, D, page, Tb, budget, kv storage,
#: segments as (slot, first position, tokens) in buffer order, and the
#: kernel's VMEM budgets and query windows patched smaller so that small
#: shapes take several KV blocks, head groups or query windows)
_PACKED_CASES = {
    # slot 0: 6-token prefill crossing the first page boundary; slot 1: a
    # decode token mid-page; slot 2 sits out; a padding tail
    "8": (8, 2, 32, 8, 6, 8, None, [(0, 5, 6), (1, 21, 1)], {}),
    "16": (8, 2, 32, 8, 6, 16, None, [(0, 5, 6), (1, 21, 1)], {}),
    # decode only: one token per slot, contexts of 1 to 6 pages, one
    # ending on a page boundary
    "decode": (8, 2, 32, 8, 6, 12, None,
               [(0, 0, 1), (1, 7, 1), (2, 8, 1), (3, 30, 1), (4, 47, 1)],
               {}),
    # decode tokens and a 30-token prefill whose context ends mid-page and
    # spans 5 pages, in KV blocks of 2 pages and query windows of 8/16
    "prefill_blocks": (8, 2, 32, 8, 6, 40, None,
                       [(0, 12, 1), (1, 3, 1), (2, 9, 30), (3, 40, 1)],
                       {"_KV_BLOCK_VMEM": 2 * 2 * 2 * (2 * 8 * 128 * 4),
                        "_Q_WINDOWS": (8, 16)}),
    # speculative proposals: 4-row segments at any row alignment, and a
    # prefill chunk behind them
    "spec": (8, 2, 32, 8, 6, 24, None,
             [(0, 10, 4), (1, 3, 4), (2, 30, 4), (3, 0, 7)], {}),
    # nothing live: every row is padding
    "padding": (8, 2, 32, 8, 6, 8, None, [], {}),
    "int8": (8, 2, 32, 8, 6, 16, jnp.int8,
             [(0, 5, 6), (1, 21, 1), (2, 40, 3)], {}),
    "fp8": (8, 2, 32, 8, 6, 16, jnp.float8_e4m3fn,
            [(0, 5, 6), (1, 21, 1), (2, 40, 3)], {}),
    # MHA at the serving model's 25 heads (G = 1), in head groups of 5
    "mha25": (25, 25, 16, 8, 4, 12, None,
              [(0, 3, 1), (1, 20, 1), (2, 6, 8)],
              {"_HEAD_GROUP_VMEM": 5 * 4 * (4 * 16 * 128 + 16 * 384)}),
    # GQA G = 4 with one KV head, decode and a chunk
    "gqa4": (4, 1, 32, 8, 6, 16, None, [(0, 17, 1), (1, 2, 9)], {}),
}


@pytest.mark.parametrize("case", list(_PACKED_CASES))
def test_paged_packed_attention_sweep(case, monkeypatch):
    """Packed ragged kernel vs its gather oracle over one flat token
    buffer: each case's segments packed in order, then a padding tail
    (tok_pos == -1, slot 0) that must emit exact zeros."""
    H, Hkv, D, page, Tb, budget, store, segs, patch = _PACKED_CASES[case]
    for name, value in patch.items():
        monkeypatch.setattr(PA, name, value)
    S = max([s for s, _, _ in segs], default=0) + 1
    P = Tb * S + 2
    ks = jax.random.split(jax.random.PRNGKey(17), 5)
    q = jax.random.normal(ks[0], (budget, H, D))
    k_pages = jax.random.normal(ks[1], (P, page, Hkv, D))
    v_pages = jax.random.normal(ks[2], (P, page, Hkv, D))
    scales = {}
    if store is not None:
        k_pages, v_pages = k_pages.astype(store), v_pages.astype(store)
        scales = {f"{x}_scale": jax.random.uniform(
            k, (P, 1, page), minval=0.005, maxval=0.02)
            for x, k in zip("kv", ks[3:])}
    bt = jnp.asarray(np.arange(1, 1 + S * Tb).reshape(S, Tb), jnp.int32)
    tok_slot = [s for s, _, n in segs for _ in range(n)]
    tok_pos = [p0 + i for _, p0, n in segs for i in range(n)]
    live = len(tok_pos)
    tok_slot = jnp.asarray(tok_slot + [0] * (budget - live), jnp.int32)
    tok_pos = jnp.asarray(tok_pos + [-1] * (budget - live), jnp.int32)
    out = PA.paged_packed_attention(q, k_pages, v_pages, bt, tok_slot,
                                    tok_pos, interpret=True, **scales)
    ref = R.paged_packed_attention_ref(q, k_pages, v_pages, bt, tok_slot,
                                       tok_pos, **scales)
    assert out.shape == (budget, H, D)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5
    assert bool(jnp.all(out[live:] == 0))        # padding rows emit zeros
    assert bool(jnp.all(ref[live:] == 0))


def test_paged_packed_attention_t_eq_slots_matches_decode_kernel():
    """The all-decode degenerate case (T == slots, one token per slot)
    must agree with the single-token decode kernel contract
    (seq_lens == tok_pos + 1)."""
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    S, H, Hkv, D, page, T = 2, 4, 2, 32, 8, 3
    q = jax.random.normal(ks[0], (S, H, D))
    kp = jax.random.normal(ks[1], (S * T + 2, page, Hkv, D))
    vp = jax.random.normal(ks[2], (S * T + 2, page, Hkv, D))
    bt = jnp.asarray(np.arange(1, 1 + S * T).reshape(S, T), jnp.int32)
    tok_slot = jnp.arange(S, dtype=jnp.int32)
    tok_pos = jnp.asarray([10, page - 1], jnp.int32)
    packed = PA.paged_packed_attention(q, kp, vp, bt, tok_slot, tok_pos,
                                       interpret=True)
    dec = PA.paged_decode_attention(q, kp, vp, bt, tok_pos + 1,
                                    interpret=True)
    assert jnp.max(jnp.abs(packed - dec)) < 2e-5


def test_paged_packed_attention_ops_dispatch():
    """CPU fallback (gather oracle) == interpret-mode packed kernel."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (6, 4, 32))
    k_pages = jax.random.normal(ks[1], (6, 8, 2, 32))
    v_pages = jax.random.normal(ks[2], (6, 8, 2, 32))
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    tok_slot = jnp.asarray([0, 0, 0, 1, 0, 0], jnp.int32)
    tok_pos = jnp.asarray([9, 10, 11, 3, -1, -1], jnp.int32)
    a = ops.paged_packed_attention(q, k_pages, v_pages, bt, tok_slot,
                                   tok_pos, use_pallas=False)
    b = ops.paged_packed_attention(q, k_pages, v_pages, bt, tok_slot,
                                   tok_pos, interpret=True)
    assert jnp.max(jnp.abs(a - b)) < 2e-5


def test_paged_chunk_attention_ops_dispatch():
    """CPU fallback (gather oracle) == interpret-mode chunked kernel."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (2, 4, 4, 32))
    k_pages = jax.random.normal(ks[1], (6, 8, 2, 32))
    v_pages = jax.random.normal(ks[2], (6, 8, 2, 32))
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([9, 3], jnp.int32)
    nv = jnp.asarray([4, 2], jnp.int32)
    a = ops.paged_chunk_attention(q, k_pages, v_pages, bt, pos, nv,
                                  use_pallas=False)
    b = ops.paged_chunk_attention(q, k_pages, v_pages, bt, pos, nv,
                                  interpret=True)
    assert jnp.max(jnp.abs(a - b)) < 2e-5


def test_paged_attention_ops_dispatch():
    """CPU fallback (gather ref) == interpret-mode kernel."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 4, 32))
    k_pages = jax.random.normal(ks[1], (6, 8, 2, 32))
    v_pages = jax.random.normal(ks[2], (6, 8, 2, 32))
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    sl = jnp.asarray([11, 7], jnp.int32)
    a = ops.paged_decode_attention(q, k_pages, v_pages, bt, sl,
                                   use_pallas=False)
    b = ops.paged_decode_attention(q, k_pages, v_pages, bt, sl,
                                   interpret=True)
    assert jnp.max(jnp.abs(a - b)) < 2e-5


def test_ops_dispatch_matches_model_attention():
    """kernels.ops CPU fallback == models.attention blockwise =="""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    a = ops.flash_attention(q, k, v, use_pallas=False)
    b = R.attention_ref(q, k, v)
    assert jnp.max(jnp.abs(a - b)) < 1e-5
    c = ops.flash_attention(q, k, v, interpret=True)
    assert jnp.max(jnp.abs(c - b)) < 1e-5
