"""Compile the main-path Pallas kernels for a described TPU v5e chip at
``gpt2-117m`` width.  Nothing runs: the TPU compiler, which is installed
with JAX, compiles for a topology that is described and not attached, and
refuses what the chip would refuse — blocks that break the (8, 128) tiling
rule, too much VMEM — which interpret mode cannot catch.

The topology is described inside a module-scope fixture, never at import
time: only one process may load the TPU library, and test collection runs
in every worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import ops
from repro.models.layers import mlp_init

CFG = get_config("gpt2-117m")
H, HKV, D = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim
PAGE, NUM_PAGES, SLOTS, BUDGET = 16, 1024, 8, 263
MAX_BLOCKS = CFG.max_seq // PAGE
KV_STORE = {"bf16": jnp.bfloat16, "int8": jnp.int8,
            "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compile cannot be read back from the persistent
    # cache without the chip, so keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, one_chip, *shapes):
    sds = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*sds).compile().as_text()


def _pools(kv):
    store = KV_STORE[kv]
    pool = ((NUM_PAGES, PAGE, HKV, D), store)
    scale = ((NUM_PAGES, 1, PAGE), jnp.float32)
    return [pool, pool] + ([scale, scale] if kv != "bf16" else [])


@pytest.mark.parametrize("kv", ["bf16", "int8", "fp8"])
def test_paged_packed_attention_compiles(one_chip, kv):
    def f(q, kp, vp, bt, ts, tp, *scales):
        ks, vs = scales if scales else (None, None)
        return ops.paged_packed_attention(q, kp, vp, bt, ts, tp, k_scale=ks,
                                          v_scale=vs, use_pallas=True)
    txt = _compile_text(
        f, one_chip, ((BUDGET, H, D), jnp.bfloat16), *_pools(kv)[:2],
        ((SLOTS, MAX_BLOCKS), jnp.int32), ((BUDGET,), jnp.int32),
        ((BUDGET,), jnp.int32), *_pools(kv)[2:])
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_attention_compiles(one_chip, kv):
    def f(q, kp, vp, bt, sl, *scales):
        ks, vs = scales if scales else (None, None)
        return ops.paged_decode_attention(q, kp, vp, bt, sl, k_scale=ks,
                                          v_scale=vs, use_pallas=True)
    txt = _compile_text(
        f, one_chip, ((SLOTS, H, D), jnp.bfloat16), *_pools(kv)[:2],
        ((SLOTS, MAX_BLOCKS), jnp.int32), ((SLOTS,), jnp.int32),
        *_pools(kv)[2:])
    assert "tpu_custom_call" in txt


def test_page_copy_compiles(one_chip):
    def f(pool, src, dst):
        return ops.copy_pages(pool, src, dst, use_pallas=True)
    txt = _compile_text(f, one_chip, _pools("bf16")[0],
                        ((4,), jnp.int32), ((4,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_fused_ln_add_compiles(one_chip):
    def f(x, a, scale, bias):
        return ops.fused_ln_add(x, a, scale, bias, kind="layernorm",
                                use_pallas=True)
    row = ((8 * 1024, CFG.d_model), jnp.bfloat16)
    vec = ((CFG.d_model,), jnp.float32)
    txt = _compile_text(f, one_chip, row, row, vec, vec)
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles(one_chip):
    def f(q, k, v):
        return ops.flash_attention(q, k, v, use_pallas=True)
    qkv = ((1, CFG.max_seq, H, D), jnp.bfloat16)
    txt = _compile_text(f, one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("max_blocks,site", [
    (2, "dual_branch_decode"),               # d_ff / (Hkv*2) = 128 lanes
    (MAX_BLOCKS, "dual_branch_decode.two_op"),   # 4-lane tiles: two ops
])
def test_dual_branch_dispatch_compiles(one_chip, max_blocks, site):
    ffn = jax.eval_shape(lambda: mlp_init(jax.random.PRNGKey(0),
                                          CFG.d_model, CFG.d_ff, CFG.mlp))

    def f(q, kp, vp, bt, sl, x, wi, wo):
        return ops.dual_branch_decode(q, kp, vp, bt, sl, x,
                                      {"wi": wi, "wo": wo}, kind=CFG.mlp,
                                      use_pallas=True)
    ops.reset_dispatch_paths()
    txt = _compile_text(
        f, one_chip, ((SLOTS, H, D), jnp.bfloat16), *_pools("bf16"),
        ((SLOTS, max_blocks), jnp.int32), ((SLOTS,), jnp.int32),
        ((SLOTS, 1, CFG.d_model), jnp.bfloat16),
        (ffn["wi"].shape, jnp.bfloat16), (ffn["wo"].shape, jnp.bfloat16))
    assert "tpu_custom_call" in txt
    assert ops.dispatch_paths() == {site: "fused-tpu"}
