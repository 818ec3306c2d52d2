"""Dual-branch (MHA||MLP) decode: logits equivalence vs the sequential
path across connection modes and decoder families, loud
``ExecutionPlan.validate`` errors for modes/phases where the branches cannot
run concurrently, the fused Pallas dispatch vs its oracle, and the
structural no-extra-collectives gate under explicit TP."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import fal
from repro.core.plan import ExecutionPlan, Phase
from repro.models import model as M
from repro.serve.paged_cache import pages_needed
from repro.serve.scheduler import EngineConfig, PagedEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the six styles split exactly on fal.mlp_input_depends_on_local_attention
DUAL_MODES = ("fal", "parallel", "ablation2")
SEQ_ONLY_MODES = ("preln", "falplus", "ablation1")

#: the sequential and dual-branch paged ticks compute the same float32
#: arithmetic, but XLA fuses the two programs differently and may reorder
#: float32 accumulations (fal's fresh-signal add, the MLA latent matmuls).
#: The logits then differ by a few ulps (at most 1.5e-6 at |logit| <= 4.5
#: on these reduced configs); a real dispatch bug moves them by O(1e-1).
LOGIT_ATOL = 2e-5


def _assert_logits_close(seq, dual, what=""):
    err = float(jnp.max(jnp.abs(seq - dual)))
    assert err <= LOGIT_ATOL, (what, err)


FAMILY_ARCHS = [("llama3.2-3b", "dense"),
                ("qwen3-moe-30b-a3b", "moe"),
                ("llava-next-mistral-7b", "vlm")]


def _paged_logits(cfg, params, toks, chunk, *, dual, page_size=8,
                  num_pages=24):
    """Drive paged_decode_step over ``toks`` in chunks under a paged plan
    with/without dual_branch; return all logits."""
    B, S = toks.shape
    T = pages_needed(S, page_size)
    plan = ExecutionPlan.single_device(Phase.PAGED, dual_branch=dual)
    cache = M.init_paged_cache(cfg, num_pages, page_size, B, "float32")
    bt = jnp.asarray(np.arange(1, 1 + B * T, dtype=np.int32).reshape(B, T))
    step = jax.jit(lambda b, c: M.paged_decode_step(params, cfg, b, c, plan))
    outs, t = [], 0
    while t < S:
        nv = min(chunk, S - t)
        padded = np.zeros((B, chunk), np.int32)
        padded[:, :nv] = np.asarray(toks[:, t:t + nv])
        lg, cache = step({"tokens": jnp.asarray(padded),
                          "pos": jnp.full((B,), t, jnp.int32),
                          "n_valid": jnp.full((B,), nv, jnp.int32),
                          "block_tables": bt}, cache)
        outs.append(lg[:, :nv])
        t += nv
    return jnp.concatenate(outs, 1)


@pytest.mark.parametrize("arch,family", FAMILY_ARCHS)
@pytest.mark.parametrize("mode", DUAL_MODES)
def test_dual_branch_bit_exact_paged(arch, family, mode):
    """Dual-branch paged decode must match sequential decode (same
    primitives, same operands, same residual-merge association; only
    XLA's fusion differs — ``LOGIT_ATOL``) for every dual-eligible style x
    decoder family."""
    cfg = get_config(arch).reduced().replace(connection=mode)
    assert cfg.family == family
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    seq = _paged_logits(cfg, params, toks, chunk=1, dual=False)
    dual = _paged_logits(cfg, params, toks, chunk=1, dual=True)
    _assert_logits_close(seq, dual, (arch, mode))


def test_dual_branch_bit_exact_chunked_prefill():
    """Branch parallelism also applies to C > 1 chunked-prefill ticks (the
    signal is then the fresh per-position export, not the per-slot cache)."""
    cfg = get_config("llama3.2-3b").reduced().replace(connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 21), 0, cfg.vocab)
    for chunk in (5, 21):
        seq = _paged_logits(cfg, params, toks, chunk=chunk, dual=False)
        dual = _paged_logits(cfg, params, toks, chunk=chunk, dual=True)
        assert bool(jnp.array_equal(seq, dual)), chunk


def test_dual_branch_bit_exact_reduced_cache_dtype():
    """Active lanes must consume this tick's FRESH activation-dtype signal —
    routing it through a bfloat16 KV-cache dtype would round it (an error
    of bf16's 2^-8, far above ``LOGIT_ATOL``) and break equivalence with
    the sequential path (regression)."""
    cfg = get_config("llama3.2-3b").reduced().replace(connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    B, S, page = 2, 12, 8
    T = pages_needed(S, page)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    bt = jnp.asarray(np.arange(1, 1 + B * T, dtype=np.int32).reshape(B, T))

    def drive(dual):
        plan = ExecutionPlan.single_device(Phase.PAGED, dual_branch=dual)
        cache = M.init_paged_cache(cfg, 24, page, B, "bfloat16")
        step = jax.jit(
            lambda b, c: M.paged_decode_step(params, cfg, b, c, plan))
        outs = []
        for t in range(S):
            lg, cache = step({"tokens": toks[:, t:t + 1],
                              "pos": jnp.full((B,), t, jnp.int32),
                              "n_valid": jnp.ones((B,), jnp.int32),
                              "block_tables": bt}, cache)
            outs.append(lg)
        return jnp.concatenate(outs, 1)

    _assert_logits_close(drive(False), drive(True))


def test_dual_branch_bit_exact_contiguous_decode():
    """decode_step (contiguous KV cache) honors plan.dual_branch too."""
    cfg = get_config("llama3.2-3b").reduced().replace(connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab)

    def drive(dual):
        plan = ExecutionPlan.single_device(Phase.DECODE, dual_branch=dual)
        cache = M.init_cache(cfg, 2, 10, "float32")
        step = jax.jit(
            lambda b, c: M.decode_step(params, cfg, b, c, plan))
        outs = []
        for t in range(10):
            lg, cache = step({"tokens": toks[:, t:t + 1],
                              "pos": jnp.full((2,), t, jnp.int32)}, cache)
            outs.append(lg)
        return jnp.concatenate(outs, 1)

    assert bool(jnp.array_equal(drive(False), drive(True)))


def test_dual_branch_mla_paged():
    """MLA (latent pages) has no fused kernel but still runs branch-parallel
    dispatch; equivalence within ``LOGIT_ATOL`` must hold there as well."""
    cfg = get_config("deepseek-v3-671b").reduced().replace(connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab)
    seq = _paged_logits(cfg, params, toks, chunk=1, dual=False)
    dual = _paged_logits(cfg, params, toks, chunk=1, dual=True)
    _assert_logits_close(seq, dual)


# --------------------------------------------------------------------------- #
# validation
# --------------------------------------------------------------------------- #
def test_dual_branch_modes_predicate():
    assert set(fal.DUAL_BRANCH_MODES) == set(DUAL_MODES)
    for m in SEQ_ONLY_MODES:
        assert fal.mlp_input_depends_on_local_attention(m)


@pytest.mark.parametrize("mode", SEQ_ONLY_MODES)
def test_validate_rejects_sequential_only_modes(mode):
    cfg = get_config("llama3.2-3b").reduced().replace(connection=mode)
    plan = ExecutionPlan.single_device(Phase.DECODE, dual_branch=True)
    with pytest.raises(ValueError, match="must assemble MHA"):
        plan.validate(cfg)


def test_validate_rejects_full_sequence_phases():
    cfg = get_config("llama3.2-3b").reduced().replace(connection="fal")
    for phase in ("train", "eval", "prefill"):
        plan = ExecutionPlan.single_device(phase, dual_branch=True)
        with pytest.raises(ValueError, match="decode-time dispatch"):
            plan.validate(cfg)
    # forward() validates, so a dual plan can never run full-sequence blocks
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
    with pytest.raises(ValueError, match="decode-time dispatch"):
        M.forward(params, cfg, {"tokens": toks},
                  ExecutionPlan.single_device(dual_branch=True))


@pytest.mark.parametrize("arch", ["whisper-small", "mamba2-370m"])
def test_validate_rejects_families_without_dual_dispatch(arch):
    """audio decoder blocks consume cross-attention, ssm blocks have no
    MHA/MLP fork — reject at validate time, not mid-trace."""
    cfg = get_config(arch).reduced()
    plan = ExecutionPlan.single_device(Phase.DECODE, dual_branch=True)
    with pytest.raises(ValueError, match="has no MHA..MLP decode dispatch"):
        plan.validate(cfg)


def test_dual_branch_bit_exact_hybrid_decode():
    """The zamba weight-shared attention block is a FAL block — dual-branch
    decode applies and stays bit-exact."""
    cfg = get_config("zamba2-1.2b").reduced().replace(connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)

    def drive(dual):
        plan = ExecutionPlan.single_device(Phase.DECODE, dual_branch=dual)
        cache = M.init_cache(cfg, 2, 8, "float32")
        step = jax.jit(lambda b, c: M.decode_step(params, cfg, b, c, plan))
        outs = []
        for t in range(8):
            lg, cache = step({"tokens": toks[:, t:t + 1],
                              "pos": jnp.full((2,), t, jnp.int32)}, cache)
            outs.append(lg)
        return jnp.concatenate(outs, 1)

    assert bool(jnp.array_equal(drive(False), drive(True)))


def test_validate_rejects_post_norms():
    cfg = get_config("gemma2-27b").reduced().replace(connection="parallel")
    plan = ExecutionPlan.single_device(Phase.DECODE, dual_branch=True)
    with pytest.raises(ValueError, match="post_norms"):
        plan.validate(cfg)


def test_engine_rejects_dual_branch_with_preln():
    cfg = get_config("llama3.2-3b").reduced().replace(connection="preln")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="must assemble MHA"):
        PagedEngine(cfg, params, EngineConfig(dual_branch=True))


# --------------------------------------------------------------------------- #
# fused kernel dispatch
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("kind", ["swiglu", "geglu", "gelu"])
def test_fused_dual_branch_kernel_matches_oracle(kind):
    """Interpret-mode fused kernel (paged gather + FFN tiles in one
    pallas_call) vs the gather ref + mlp_apply oracle.  d_ff splits into
    Hkv*T = 8 tiles of 128 lanes, so the dispatcher selects the fused
    kernel."""
    from repro.kernels import ops, ref as R
    from repro.models.layers import mlp_apply, mlp_init
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    B, H, Hkv, D, page, T, Dm, F = 2, 8, 2, 32, 8, 4, 64, 1024
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jax.random.normal(ks[1], (T * B + 2, page, Hkv, D))
    vp = jax.random.normal(ks[2], (T * B + 2, page, Hkv, D))
    bt = jnp.asarray(np.arange(1, 1 + B * T).reshape(B, T), jnp.int32)
    sl = jnp.asarray([(T - 1) * page + 3, page], jnp.int32)
    x = jax.random.normal(ks[3], (B, 1, Dm))
    ffn = mlp_init(ks[4], Dm, F, kind)
    ops.reset_dispatch_paths()
    a, y = ops.dual_branch_decode(q, kp, vp, bt, sl, x, ffn, kind=kind,
                                  interpret=True)
    assert ops.dispatch_paths() == {"dual_branch_decode": "interpret"}
    a_ref = R.paged_attention_ref(q, kp, vp, bt, sl)
    y_ref = mlp_apply(ffn, x, kind)
    assert jnp.max(jnp.abs(a - a_ref)) < 2e-5
    assert jnp.max(jnp.abs(y - y_ref)) < 5e-5


def test_fused_kernel_falls_back_on_non_divisible_dff():
    """d_ff not divisible into Hkv*T tiles -> dispatcher issues the two
    branches separately instead of erroring, under their own label."""
    from repro.kernels import ops
    from repro.models.layers import mlp_init
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (1, 4, 32))
    kp = jax.random.normal(ks[1], (4, 8, 2, 32))
    vp = jax.random.normal(ks[2], (4, 8, 2, 32))
    bt = jnp.asarray([[1, 2]], jnp.int32)
    sl = jnp.asarray([9], jnp.int32)
    x = jax.random.normal(ks[3], (1, 1, 48))
    ffn = mlp_init(ks[4], 48, 98, "gelu")       # 98 % (Hkv*T = 2*2) != 0
    ops.reset_dispatch_paths()
    a, y = ops.dual_branch_decode(q, kp, vp, bt, sl, x, ffn, kind="gelu",
                                  interpret=True)
    assert ops.dispatch_paths() == {"dual_branch_decode.two_op": "interpret"}
    assert a.shape == (1, 4, 32) and y.shape == (1, 1, 48)
    # and the separate-branch results still match the oracles
    from repro.kernels import ref as R
    from repro.models.layers import mlp_apply
    assert jnp.max(jnp.abs(a - R.paged_attention_ref(q, kp, vp, bt, sl))) \
        < 2e-5
    assert jnp.max(jnp.abs(y - mlp_apply(ffn, x, "gelu"))) < 5e-5


# --------------------------------------------------------------------------- #
# structural gate: no extra collectives under explicit TP
# --------------------------------------------------------------------------- #
def test_dual_branch_no_extra_collectives_explicit_tp():
    """Lower one steady-state block's paged decode tick under a 2-device
    explicit-TP shard_map with and without dual_branch: both must pay
    exactly ONE all-reduce (the fused MHA+MLP partial-sum assemble) — the
    branch-parallel dispatch adds no collectives.  Subprocess keeps the
    main suite single-device (conftest contract)."""
    script = """
import jax
from repro.core import tp
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ('model',))
counts = tp.assert_dual_no_extra_collectives(mesh, modes=('fal', 'parallel'))
assert set(counts) == {'fal', 'parallel'}
print('OK', counts)
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
