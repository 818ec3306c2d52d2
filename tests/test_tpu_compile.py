"""Compile the main-path Pallas kernels for a described TPU v5e chip at
``gpt2-117m`` width, and the train step and the packed serving tick at
reduced widths to check the layer scopes their ops carry.  Nothing runs:
the TPU compiler, which is installed with JAX, compiles for a topology
that is described and not attached, and refuses what the chip would
refuse — blocks that break the (8, 128) tiling rule, too much VMEM —
which interpret mode cannot catch.

The topology is described inside a module-scope fixture, never at import
time: only one process may load the TPU library, and test collection runs
in every worker.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import ops
from repro.models.layers import mlp_init
from repro.obs.trace import innermost_scope

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


#: the serving cell's widths (``gpt2-1.5b-fal.serve.chat``): GPT-2 XL's 25
#: heads of 64, 128-token pages, 152 pages, 36 slots of 8 blocks, and a
#: 291-token budget
SERVE_H, SERVE_D, SERVE_PAGE, SERVE_PAGES = 25, 64, 128, 152
SERVE_SLOTS, SERVE_BLOCKS, SERVE_BUDGET = 36, 8, 291
_DEF = re.compile(r"%(\S+) = (\w+)\[([\d,]*)\]\S* (\S+?)\((.*)")


@pytest.mark.parametrize("kv", ["bf16", "int8", "fp8"])
def test_paged_packed_attention_reads_the_pool_in_place(one_chip, kv):
    """At the serving cell's widths the packed kernel's custom call takes
    each page pool itself, or a bitcast of it: no transpose or copy of a
    whole (152, ..., 25, 64) pool runs before it."""
    store = KV_STORE[kv]
    pool = ((SERVE_PAGES, SERVE_PAGE, SERVE_H, SERVE_D), store)
    scale = ((SERVE_PAGES, 1, SERVE_PAGE), jnp.float32)

    def f(q, kp, vp, bt, ts, tp, *scales):
        ks, vs = scales if scales else (None, None)
        return ops.paged_packed_attention(q, kp, vp, bt, ts, tp, k_scale=ks,
                                          v_scale=vs, use_pallas=True)
    txt = _compile_text(
        f, one_chip, ((SERVE_BUDGET, SERVE_H, SERVE_D), jnp.bfloat16),
        pool, pool, ((SERVE_SLOTS, SERVE_BLOCKS), jnp.int32),
        ((SERVE_BUDGET,), jnp.int32), ((SERVE_BUDGET,), jnp.int32),
        *([scale, scale] if kv != "bf16" else []))
    defs = {}
    for line in txt.splitlines():
        m = _DEF.search(line)
        if m:
            defs[m.group(1)] = (m.group(3), m.group(4), m.group(5))
    pools = {name: (op, rest) for name, (dims, op, rest) in defs.items()
             if dims.startswith(f"{SERVE_PAGES},")
             and {str(SERVE_H), str(SERVE_D)} <= set(dims.split(","))}
    assert {op for op, _ in pools.values()} <= {"parameter", "bitcast"}, \
        {n: op for n, (op, _) in pools.items()}
    call = [rest for _, op, rest in defs.values() if op == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in rest]
    assert len(call) == 1
    fed = [p for p, (op, _) in pools.items() if op == "bitcast"
           and re.search(rf"%{re.escape(p)}\b", call[0])]
    assert len(fed) == 2, (fed, call[0][:300])
    for p in fed:
        src = re.match(r"%(\S+?)\)", pools[p][1]).group(1)
        assert pools[src][0] == "parameter"


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


# --------------------------------------------------------------------------- #
# layer scopes in the compiled programs (obs.trace.LAYER_SCOPES)
# --------------------------------------------------------------------------- #
SCOPED = ("dot", "convolution", "custom-call")
_OPCODE = re.compile(r"= \S+ ([\w-]+)\(")
_STACK = re.compile(r'op_name="([^"]*)"')
FAL = get_config("gpt2-774m").reduced().replace(connection="fal")


def _ops(txt, kinds):
    """(opcode, name stack) of each instruction of compiled HLO text whose
    opcode starts with one of ``kinds``."""
    out = []
    for line in txt.splitlines():
        m = _OPCODE.search(line)
        if m and m.group(1).startswith(kinds):
            st = _STACK.search(line)
            out.append((m.group(1), st.group(1) if st else ""))
    return out


def _assert_each_op_has_a_layer(txt):
    ops_ = _ops(txt, SCOPED)
    assert ops_
    bare = [o for o in ops_ if innermost_scope(o[1]) is None]
    assert not bare, bare[:5]
    return ops_


def _train_step_text(topo, n_model):
    from repro.core.plan import ExecutionPlan
    from repro.launch import mesh as MX
    from repro.optim import adamw
    from repro.train import step as tstep
    ocfg = adamw.AdamWConfig()
    mesh = MX.make_mesh((1, n_model), ("data", "model"),
                        devices=topo.devices[:n_model])
    plan = (ExecutionPlan.from_mesh(mesh, tp="explicit") if n_model > 1
            else ExecutionPlan.single_device())
    shapes = jax.eval_shape(lambda k: tstep.init_state(k, FAL, ocfg),
                            jax.random.PRNGKey(0))
    st_sh = MX.shardings_for(mesh, MX.state_specs(shapes, FAL))
    state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, st_sh)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (4, 32), jnp.int32,
        sharding=NamedSharding(mesh, MX.batch_spec(mesh)))}
    return jax.jit(tstep.make_train_step(FAL, ocfg, plan)).lower(
        state, batch).compile().as_text()


def test_train_step_ops_carry_one_layer_scope(one_chip, topo):
    ops_ = _assert_each_op_has_a_layer(_train_step_text(topo, 1))
    scopes = {innermost_scope(s) for _, s in ops_}
    assert {"attn", "mlp", "head", "embed"} <= scopes
    # backward ops keep the forward's scopes inside transpose(jvp(...))
    assert any("transpose(" in s and innermost_scope(s) == "attn"
               for _, s in ops_)


def test_tp_train_step_ops_and_all_reduces_carry_layer_scopes(one_chip,
                                                             topo):
    """Explicit TP over the described 2x2: every matmul has a layer, and
    the all-reduces ``blocks._assemble`` emits (block 0's two, one per
    later block under FAL) and their transposes carry ``assemble``."""
    txt = _train_step_text(topo, 4)
    _assert_each_op_has_a_layer(txt)
    asm = [s for _, s in _ops(txt, ("all-reduce",))
           if innermost_scope(s) == "assemble"]
    fwd = [s for s in asm if "transpose(" not in s]
    bwd = [s for s in asm if "transpose(" in s]
    assert len(fwd) == len(bwd) == FAL.n_layers + 1, asm


def test_packed_tick_ops_carry_one_layer_scope(one_chip):
    """The serving tick with the Pallas kernels: the paged attention
    kernel under ``attn``, the page writes under ``kv_write``, the
    sampler under ``sample``.  The paged layer scan runs under
    ``kv_write`` too (its pool slices and updates), so no matmul or
    kernel of a layer's body may fall through to the scan's scope."""
    from repro.core.plan import ExecutionPlan
    from repro.models import model as M
    from repro.serve.scheduler import make_packed_step
    S, T, page, pages, Tb = 4, 19, 16, 32, 8
    sd = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.eval_shape(lambda k: M.init_params(k, FAL),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: M.init_paged_cache(FAL, pages, page, S,
                                                      "bfloat16"))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    step = make_packed_step(
        FAL, ExecutionPlan.single_device().with_kernels("pallas"))
    txt = step.lower(jax.tree.map(sd, params), jax.tree.map(sd, cache),
                     i32(T), i32(T), i32(T), i32(S, Tb), i32(S), f32(S),
                     i32(S), f32(S), i32(S), i32(S)).compile().as_text()
    ops_ = _assert_each_op_has_a_layer(txt)
    kernel = [s for op, s in ops_ if op == "custom-call"
              and "paged_packed_attention" in s]
    assert kernel and {innermost_scope(s) for s in kernel} == {"attn"}
    leaked = [o for o in ops_ if innermost_scope(o[1]) == "kv_write"
              and "attn/kv_write" not in o[1]]
    assert not leaked, leaked[:5]
    every = {innermost_scope(s) for line in txt.splitlines()
             for s in _STACK.findall(line)}
    assert {"kv_write", "sample", "head", "embed"} <= every


def test_held_expert_share_compiles_to_grouped_kernels(one_chip):
    """Moonlight's held share at its real widths (8 of 64 experts of
    1408, top 6, one row of 8192 tokens of 2048), forward and backward:
    the grouped matmuls over the held pairs compile to the chip's grouped
    kernel (a Mosaic custom call), not to dense products over the
    49,152-row buffer, and the routing and combine around them fit."""
    from repro.models import moe as MOE
    cfg = get_config("moonlight-16b-a3b").replace(experts_held=8,
                                                  dtype="bfloat16")
    n, d, f, E = 8, cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    bf = jnp.bfloat16

    def f_(x, router, e_bias, wi, wg, wo, sw, sg, so):
        p = {"router": router, "e_bias": e_bias, "wi": wi, "wg": wg,
             "wo": wo, "shared": {"wi": sw, "wg": sg, "wo": so}}
        y, (aux, _) = MOE.moe_apply_held(p, cfg, x)
        return jnp.sum(y.astype(jnp.float32)) + aux

    fs = 2 * f
    txt = _compile_text(jax.grad(f_, argnums=(0, 1, 3, 4, 5)), one_chip,
                        ((1, 8192, d), bf), ((d, E), bf), ((E,), bf),
                        ((n, d, f), bf), ((n, d, f), bf), ((n, f, d), bf),
                        ((d, fs), bf), ((d, fs), bf), ((fs, d), bf))
    kernels = re.findall(r'custom_call_target="tpu_custom_call"', txt)
    # three products forward, each with a rows' and a weights' gradient
    assert len(kernels) >= 9, len(kernels)
    # no XLA matmul over the buffer: its products are the kernels'
    dense = [ln for ln in txt.splitlines() if "49152" in ln
             and re.search(r"= \S+ (dot|convolution)\(", ln)]
    assert not dense, dense[:3]
