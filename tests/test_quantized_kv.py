"""Quantized KV pages (EngineConfig.kv_dtype): per-page-row scale
correctness against the fp32 oracle, COW scale preservation, and
engine-level greedy/prefix-hit token identity across connection styles.

The format under test: int8/fp8 K/V pools (P, page, Hkv, Dh) plus
(P, 1, page) fp32 ``k_scale``/``v_scale`` pools — ONE scale per cached token
row, shared across KV heads, history-free (a row's scale depends only on
that row's values), so COW page copies and prefix-cache shares stay
bit-exact and idempotent.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import ops, ref
from repro.kernels import paged_attention as PA
from repro.models import attention as A
from repro.models import model as M
from repro.serve import sampling as SP
from repro.serve.scheduler import EngineConfig, PagedEngine, ServeRequest

SIX_STYLES = ("preln", "parallel", "fal", "falplus", "ablation1",
              "ablation2")


# --------------------------------------------------------------------------- #
# quantize / dequantize round trip
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("store,bound", [(jnp.int8, 0.008),
                                         (jnp.float8_e4m3fn, 0.07)])
def test_quant_rows_roundtrip_bounded(store, bound):
    """Per-token-row quantization: dequantized rows land within the grid's
    relative error bound of the originals (int8: amax/127 grid -> half a
    step is ~0.4% of amax; fp8 e4m3: ~4% relative)."""
    vals = jax.random.normal(jax.random.PRNGKey(0), (6, 5, 4, 32))
    q, s = A._quant_rows(vals, store)
    deq = q.astype(store).astype(jnp.float32) * s[..., None, None]
    amax = jnp.max(jnp.abs(vals), axis=(-2, -1), keepdims=True)
    rel = jnp.max(jnp.abs(deq - vals) / amax)
    assert float(rel) < bound, float(rel)
    # history-free: re-quantizing the dequantized values is a fixed point
    # in scale (same amax row -> same scale) for int8's exact grid
    if store == jnp.int8:
        q2, s2 = A._quant_rows(deq, store)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s), rtol=1e-6)


def test_quantized_oracle_matches_manual_dequant():
    """The paged oracles' in-gather dequant == gather-then-multiply by
    hand: the scale application point cannot drift."""
    key = jax.random.PRNGKey(1)
    P, page, Hkv, Dh, H, B, T = 12, 8, 2, 16, 4, 2, 3
    ks = jax.random.split(key, 6)
    kp = jax.random.randint(ks[0], (P, page, Hkv, Dh), -127, 128, jnp.int8)
    vp = jax.random.randint(ks[1], (P, page, Hkv, Dh), -127, 128, jnp.int8)
    ksc = jax.random.uniform(ks[2], (P, 1, page), minval=0.005, maxval=0.05)
    vsc = jax.random.uniform(ks[3], (P, 1, page), minval=0.005, maxval=0.05)
    bt = jnp.arange(1, 1 + B * T).reshape(B, T)
    q = jax.random.normal(ks[4], (B, H, Dh))
    seq = jnp.array([9, 20])

    def dq(pages, sc):
        return (pages.astype(jnp.float32)
                * sc[:, 0, :, None, None]).astype(jnp.float32)

    got = ref.paged_attention_ref(q, kp, vp, bt, seq, k_scale=ksc,
                                  v_scale=vsc)
    want = ref.paged_attention_ref(q, dq(kp, ksc), dq(vp, vsc), bt, seq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("store", ["int8", "fp8"])
def test_quantized_kernels_match_oracles_interpret(store):
    """All three paged Pallas kernels dequantize in the DMA-to-VMEM step:
    interpret-mode outputs match the gather-based oracles."""
    key = jax.random.PRNGKey(2)
    P, page, Hkv, Dh, H = 16, 16, 4, 32, 8
    B, T = 3, 4
    ks = jax.random.split(key, 8)
    if store == "int8":
        kp = jax.random.randint(ks[0], (P, page, Hkv, Dh), -120, 120,
                                jnp.int8)
        vp = jax.random.randint(ks[1], (P, page, Hkv, Dh), -120, 120,
                                jnp.int8)
    else:
        kp = jax.random.normal(ks[0], (P, page, Hkv, Dh)).astype(
            jnp.float8_e4m3fn)
        vp = jax.random.normal(ks[1], (P, page, Hkv, Dh)).astype(
            jnp.float8_e4m3fn)
    ksc = jax.random.uniform(ks[2], (P, 1, page), minval=0.005, maxval=0.02)
    vsc = jax.random.uniform(ks[3], (P, 1, page), minval=0.005, maxval=0.02)
    bt = jax.random.permutation(ks[4], jnp.arange(1, P))[:B * T].reshape(B, T)

    q = jax.random.normal(ks[5], (B, H, Dh))
    seq = jnp.array([17, 33, 64])
    np.testing.assert_allclose(
        np.asarray(PA.paged_decode_attention(q, kp, vp, bt, seq,
                                             k_scale=ksc, v_scale=vsc,
                                             interpret=True)),
        np.asarray(ref.paged_attention_ref(q, kp, vp, bt, seq, k_scale=ksc,
                                           v_scale=vsc)), atol=2e-5)

    C = 4
    qc = jax.random.normal(ks[6], (B, C, H, Dh))
    pos = jnp.array([5, 17, 40])
    nv = jnp.array([4, 1, 2])
    np.testing.assert_allclose(
        np.asarray(PA.paged_chunk_attention(qc, kp, vp, bt, pos, nv,
                                            k_scale=ksc, v_scale=vsc,
                                            interpret=True)),
        np.asarray(ref.paged_chunk_attention_ref(qc, kp, vp, bt, pos, nv,
                                                 k_scale=ksc,
                                                 v_scale=vsc)), atol=2e-5)

    Tt = 8
    qt = jax.random.normal(ks[7], (Tt, H, Dh))
    tok_slot = jnp.array([0, 0, 1, 2, 2, 2, 0, 0], jnp.int32)
    tok_pos = jnp.array([5, 6, 17, 40, 41, 42, -1, -1], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(PA.paged_packed_attention(qt, kp, vp, bt, tok_slot,
                                             tok_pos, k_scale=ksc,
                                             v_scale=vsc, interpret=True)),
        np.asarray(ref.paged_packed_attention_ref(qt, kp, vp, bt, tok_slot,
                                                  tok_pos, k_scale=ksc,
                                                  v_scale=vsc)), atol=2e-5)


def test_quantized_logit_error_bounded():
    """End-to-end accuracy: a quantized int8 paged forward's logits land
    within a bounded max-abs error of the unquantized engine's on the same
    tokens (the kv_dtype knob trades bounded logit error for HBM)."""
    cfg = get_config("llama3.2-3b").reduced().replace(connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 12), 1, cfg.vocab)
    batch = dict(tokens=toks.astype(jnp.int32)[0][None],
                 pos=jnp.array([0]), n_valid=jnp.array([12]),
                 block_tables=jnp.array([[1, 2]], jnp.int32))
    out = {}
    for kv in ("", "int8"):
        cache = M.init_paged_cache(cfg, 8, 8, 1, "float32", kv_dtype=kv)
        logits, _ = M.paged_decode_step(params, cfg, dict(batch), cache)
        out[kv] = np.asarray(logits)
    err = np.max(np.abs(out["int8"] - out[""]))
    ref_mag = np.max(np.abs(out[""]))
    assert err < A.INT8_LOGIT_RTOL * ref_mag, (err, ref_mag)


# --------------------------------------------------------------------------- #
# cache structure + COW
# --------------------------------------------------------------------------- #
def test_init_paged_cache_kv_dtypes():
    cfg = get_config("llama3.2-3b").reduced()
    for kv, dt, scaled in (("", "float32", False), ("bf16", "bfloat16",
                                                    False),
                           ("int8", "int8", True),
                           ("fp8", "float8_e4m3fn", True)):
        c = M.init_paged_cache(cfg, 8, 8, 2, "float32", kv_dtype=kv)
        assert str(c["block0"]["k"].dtype) == dt, kv
        assert ("k_scale" in c["block0"]) == scaled, kv
        if scaled:
            assert c["block0"]["k_scale"].shape == (8, 1, 8)
            assert c["blocks"]["v_scale"].shape == (cfg.n_layers - 1, 8, 1,
                                                    8)
    with pytest.raises(ValueError):
        M.init_paged_cache(cfg, 8, 8, 2, "float32", kv_dtype="int4")


def test_quantized_kv_rejected_for_mla():
    cfg = get_config("deepseek-v3-671b").reduced()
    assert cfg.use_mla
    with pytest.raises(NotImplementedError):
        M.init_paged_cache(cfg, 8, 8, 2, "float32", kv_dtype="int8")


def test_page_copy_preserves_scales_bit_exact():
    """COW over a quantized cache: the (P, 1, page) scale pools ride the same
    page-copy as the K/V pools, and the copied rows are bit-identical."""
    key = jax.random.PRNGKey(4)
    P, page = 10, 8
    sc = jax.random.uniform(key, (P, 1, page), minval=1e-4, maxval=2.0)
    src = jnp.array([2, 5])
    dst = jnp.array([7, 9])
    want = ref.copy_pages_ref(sc, src, dst)
    got = PA.page_copy(sc, src, dst, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got)[np.asarray(dst)],
                          np.asarray(sc)[np.asarray(src)])


def test_copy_paged_pages_quantized_all_layers():
    """model.copy_paged_pages over a quantized cache copies every pool —
    narrow K/V AND fp32 scales — in block0 and the stacked layers, bit
    exactly, and touches no other page."""
    cfg = get_config("llama3.2-3b").reduced()
    cache = M.init_paged_cache(cfg, 8, 8, 2, "float32", kv_dtype="int8")
    k = jax.random.PRNGKey(5)
    cache = jax.tree.map(
        lambda a: jax.random.randint(k, a.shape, -120, 120, jnp.int32)
        .astype(a.dtype) if a.dtype == jnp.int8 else
        jax.random.uniform(k, a.shape, a.dtype)
        if a.dtype == jnp.float32 else a, cache)
    src, dst = jnp.array([2, 3]), jnp.array([5, 6])
    new = M.copy_paged_pages(cache, src, dst)
    for name in ("k", "v", "k_scale", "v_scale"):
        b0, nb0 = np.asarray(cache["block0"][name]), \
            np.asarray(new["block0"][name])
        assert np.array_equal(nb0[np.asarray(dst)], b0[np.asarray(src)]), \
            name
        keep = [p for p in range(8) if p not in (5, 6)]
        assert np.array_equal(nb0[keep], b0[keep]), name
        bs, nbs = np.asarray(cache["blocks"][name]), \
            np.asarray(new["blocks"][name])
        assert np.array_equal(nbs[:, np.asarray(dst)],
                              bs[:, np.asarray(src)]), name


# --------------------------------------------------------------------------- #
# engine-level identity
# --------------------------------------------------------------------------- #
def _req(rid, prompt, max_new=6, greedy=True):
    sp = SP.SamplingParams() if greedy else SP.SamplingParams(
        temperature=0.9, top_k=50, seed=rid)
    return ServeRequest(rid=rid, prompt=np.asarray(prompt, np.int64),
                        max_new=max_new, sampling=sp)


def _run_engine(cfg, params, prompts, **ecfg_kw):
    base = dict(page_size=8, num_pages=48, slots=2, prefill_chunk=8,
                max_seq=64, cache_dtype="float32")
    base.update(ecfg_kw)
    eng = PagedEngine(cfg, params, EngineConfig(**base))
    for i, p in enumerate(prompts):
        eng.submit(_req(i, p))
    eng.run()
    return {r.rid: tuple(r.generated) for r in eng.finished}, eng


def test_quantized_greedy_identity_bench_dims():
    """kv_dtype=int8 greedy token streams == the default engine's at the
    serving bench's model dims.  Cross-dtype argmax identity is a
    workload-level property — random-init logits hit a near-tie the
    storage rounding can flip roughly once per hundred greedy tokens,
    forking the stream — so bench_serving gates exact identity on a
    bounded workload plus measured fidelity floors on the long labels;
    this test pins one verified workload plus the byte-pressure stats
    invariants."""
    cfg = get_config("gpt2-117m").replace(
        n_layers=4, d_model=256, n_heads=8, n_kv_heads=8, d_ff=1024,
        vocab=2048, max_seq=512, dtype="float32", param_dtype="float32",
        remat=False, attn_block_q=64, attn_block_k=128, connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [np.arange(11, 11 + n) for n in (5, 9, 13)]
    out_ref, eng_ref = _run_engine(cfg, params, prompts, page_size=16,
                                   num_pages=48, max_seq=160)
    out_b16, _ = _run_engine(cfg, params, prompts, page_size=16,
                             num_pages=48, max_seq=160, kv_dtype="bf16")
    out_q, eng_q = _run_engine(cfg, params, prompts, page_size=16,
                               num_pages=48, max_seq=160, kv_dtype="int8")
    assert out_q == out_ref
    assert out_b16 == out_ref
    st = eng_q.stats()["pages"]
    assert st["page_bytes"] > 0
    assert st["peak_bytes_in_use"] == st["peak_in_use"] * st["page_bytes"]
    # equal num_pages, ~4x fewer bytes per page than the float32 default
    # (2 int8 pools + 2 fp32 scale rows vs 2 fp32 pools)
    assert eng_ref.stats()["pages"]["page_bytes"] > 3 * st["page_bytes"]


@pytest.mark.parametrize("conn", SIX_STYLES)
def test_quantized_prefix_hit_identity_styles(conn):
    """Prefix-cache hit vs cold prefill under kv_dtype=int8: shared
    quantized pages (values + scales) adopted at admission must reproduce
    the cold engine's tokens bit-exactly, for every connection style —
    the history-free per-row scales make cached pages position-content
    pure, so a hit is indistinguishable from a re-prefill."""
    cfg = get_config("llama3.2-3b").reduced().replace(connection=conn)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    sysp = np.random.default_rng(3).integers(1, cfg.vocab, 16)  # 2 pages
    tail = np.random.default_rng(5).integers(1, cfg.vocab, 5)
    prompt = np.concatenate([sysp, tail])

    hot_out, hot = _run_engine(cfg, params, [sysp], kv_dtype="int8",
                               prefix_cache=True)
    probe = _req(2, prompt)
    hot.submit(probe)
    hot.run()
    assert probe.prefix_hit_tokens == 16, conn

    cold_out, _ = _run_engine(cfg, params, [prompt], kv_dtype="int8",
                              prefix_cache=True)
    assert tuple(probe.generated) == cold_out[0], conn
    hot.pcache.clear()
    assert hot.allocator.in_use == 0


def test_quantized_kernel_dispatch_telemetry():
    """Quantized paged dispatches trace under ``<site>.int8`` — runtime
    telemetry separates the quantized engine's kernel path rows.  The
    registry records at jit-trace time, so this test's engines use dims
    no other test shares (a cached executable would skip the trace)."""
    ops.reset_dispatch_paths()
    cfg = get_config("llama3.2-3b").reduced().replace(connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [np.arange(1, 10)]
    dims = dict(page_size=4, max_seq=36)
    _run_engine(cfg, params, prompts, kv_dtype="int8", **dims)
    paths = ops.dispatch_paths()
    assert "paged_packed_attention.int8" in paths, paths
    _run_engine(cfg, params, prompts, **dims)
    paths = ops.dispatch_paths()
    assert "paged_packed_attention" in paths, paths


def test_quantized_spec_decode_identity():
    """Self-speculative decoding over a quantized cache: draft, verify and
    rollback all read/write int8 pages + scale pools; greedy streams must
    stay identical to the non-spec quantized engine."""
    cfg = get_config("llama3.2-3b").reduced().replace(connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab, n) for n in (5, 9)]
    out_plain, _ = _run_engine(cfg, params, prompts, kv_dtype="int8")
    out_spec, eng = _run_engine(cfg, params, prompts, kv_dtype="int8",
                                spec_tokens=3, draft_blocks=1)
    assert out_spec == out_plain
    assert eng.stats()["dispatches_per_tick"] == 1.0
