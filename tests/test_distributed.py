"""Distributed behaviour tests (forced host devices via subprocess so the
rest of the suite keeps seeing 1 device).

Covers: TP all-reduce halving on the unified DecoderLM blocks (the paper's
claim, asserted structurally on lowered HLO), explicit-TP logits equivalence
across all six connection modes — replicated AND sequence-parallel
(ExecutionPlan sp=True) — the SP reduce-scatter bytes contract, the
shard_map train step, sharded-MoE == oracle, and a full-config dry-run
lower+compile.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(script, devices=8, timeout=600):
    # JAX_PLATFORMS=cpu: on hosts with libtpu installed but no TPU attached,
    # leaving the platform unset makes the subprocess hang on TPU-metadata
    # probes and die; the forced host-device count only applies to CPU anyway
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_tp_allreduce_halving():
    """Structural Fig 2 on the unified DecoderLM blocks: fal lowers to
    exactly ONE all-reduce per steady-state block (scan body), preln to two,
    with block 0 unscanned (fal pays its one extra assemble there)."""
    out = run_py("""
import jax, jax.numpy as jnp, json
from repro.core import tp
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('model',))
res = {}
for mode in ['preln', 'fal', 'parallel', 'falplus', 'ablation1', 'ablation2']:
    init, fwd = tp.make_tp_forward(mesh, 4, 64, 256, 8, mode)
    p = init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    txt = fwd.lower(p, x).compile().as_text()
    res[mode] = tp.count_collectives(txt).get('all-reduce', 0)
print(json.dumps(res))
""")
    res = json.loads(out.strip().splitlines()[-1])
    # block0 unscanned + scan body (counted once):
    # preln: 2 + 2;  fal: 2 (block0 assembles a1) + 1;  parallel: 1 + 1;
    # ablation1 normalises its OWN attention -> assembled like preln;
    # ablation2: block0 keeps the direct connection (2), later blocks fuse
    assert res["preln"] == 4
    assert res["fal"] == 3
    assert res["parallel"] == 2
    assert res["falplus"] == 4
    assert res["ablation1"] == 4
    assert res["ablation2"] == 3


def test_sp_reduce_scatter_structure():
    """Sequence-parallel contract on lowered HLO: each replicated
    all-reduce becomes exactly one reduce-scatter at 1/tp the bytes (block 0
    under fal/falplus keeps its ONE true all-reduce — the first-attention
    export), so ar_sp + tp * rs_sp == ar_replicated at equal reduce count."""
    out = run_py("""
import jax, jax.numpy as jnp, json
from repro.core import tp
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('model',))
res = {}
for mode in ['preln', 'fal', 'parallel', 'falplus']:
    row = {}
    for sp in (False, True):
        init, fwd = tp.make_tp_forward(mesh, 4, 64, 256, 8, mode, sp=sp)
        p = init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
        txt = fwd.lower(p, x).compile().as_text()
        row['sp' if sp else 'repl'] = {
            'n': tp.count_collectives(txt), 'b': tp.collective_bytes(txt)}
    res[mode] = row
print(json.dumps(res))
""")
    res = json.loads(out.strip().splitlines()[-1])
    tp_size = 8
    for mode, row in res.items():
        ar_n = row["repl"]["n"].get("all-reduce", 0)
        ar_b = row["repl"]["b"].get("all-reduce", 0)
        assert not row["repl"]["n"].get("reduce-scatter")
        sp_ar_n = row["sp"]["n"].get("all-reduce", 0)
        sp_rs_n = row["sp"]["n"].get("reduce-scatter", 0)
        sp_ar_b = row["sp"]["b"].get("all-reduce", 0)
        sp_rs_b = row["sp"]["b"].get("reduce-scatter", 0)
        # equal reduce-collective count; bytes cut by exactly tp_size
        assert sp_ar_n + sp_rs_n == ar_n, (mode, row)
        assert sp_ar_b + tp_size * sp_rs_b == ar_b, (mode, row)
        # only fal/falplus block 0 pays the full all-reduce (signal export)
        assert sp_ar_n == (1 if mode in ("fal", "falplus") else 0), \
            (mode, row)
        # every reduce-scatter is paired with an all-gather of an LN region
        assert row["sp"]["n"].get("all-gather", 0) >= sp_rs_n - 1, (mode, row)


def test_tp_forward_matches_replicated():
    """tp_size=1 really is the same code path: the 8-way shard_map stack
    must reproduce the 1-way stack bit-for-bit (up to psum reassociation)."""
    out = run_py("""
import jax, jax.numpy as jnp
from repro.core import tp
from repro.launch.mesh import make_mesh
mesh1 = make_mesh((1,), ('model',))
mesh8 = make_mesh((8,), ('model',))
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
for mode in ['preln', 'fal']:
    init1, fwd1 = tp.make_tp_forward(mesh1, 3, 64, 256, 8, mode)
    init8, fwd8 = tp.make_tp_forward(mesh8, 3, 64, 256, 8, mode)
    p = init1(jax.random.PRNGKey(0))
    import numpy as np
    y1 = np.asarray(fwd1(p, x)); y8 = np.asarray(fwd8(p, x))
    err = float(np.max(np.abs(y1 - y8)))
    assert err < 1e-4, (mode, err)
print('OK')
""")
    assert "OK" in out


def test_model_explicit_tp_all_modes_matches_single_device():
    """Real DecoderLM logits under the explicit partial-sum TP stack ==
    single-device forward, for ALL six connection modes — replicated AND
    sequence-parallel (the six-mode SP equivalence of the plan redesign)."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, VALID_CONNECTIONS
from repro.core.plan import ExecutionPlan
from repro.models import model as M
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 500)
for sp in (False, True):
    for mode in VALID_CONNECTIONS:
        cfg = get_config('llama3.2-3b').reduced().replace(
            connection=mode, n_kv_heads=4)
        plan = ExecutionPlan.from_mesh(mesh, tp='explicit',
                                       sp=sp).validate(cfg)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        b = {'tokens': toks % cfg.vocab}
        ref, _, _ = M.forward(params, cfg, b)
        with mesh:
            y, _, _ = jax.jit(lambda p, b: M.forward(p, cfg, b, plan))(
                params, b)
        err = float(np.max(np.abs(np.asarray(y) - np.asarray(ref))))
        assert err < 5e-4, (sp, mode, err)
print('OK')
""", timeout=900)
    assert "OK" in out


def test_model_explicit_tp_moe_mla_windows():
    """Explicit TP over the rest of the decoder family: MoE partial-sum
    experts (qwen3-moe), MLA + shared experts (deepseek), sliding-window +
    post-norms (gemma2).  qwen3-moe/gemma2 reduced have n_kv_heads=2 <
    tp_size=4, so this also covers the Megatron KV-replication fallback —
    replicated and sequence-parallel."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan
from repro.models import model as M
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 500)
cases = [('qwen3-moe-30b-a3b', {}),
         ('deepseek-v3-671b', {}),
         ('gemma2-27b', {})]
for sp in (False, True):
    for arch, over in cases:
        cfg = get_config(arch).reduced().replace(connection='fal', **over)
        plan = ExecutionPlan.from_mesh(mesh, tp='explicit',
                                       sp=sp).validate(cfg)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        b = {'tokens': toks % cfg.vocab}
        ref, _, _ = M.forward(params, cfg, b)
        with mesh:
            y, _, _ = jax.jit(lambda p, b: M.forward(p, cfg, b, plan))(
                params, b)
        err = float(np.max(np.abs(np.asarray(y) - np.asarray(ref))))
        assert err < 5e-4, (sp, arch, err)
print('OK')
""", timeout=900)
    assert "OK" in out


def test_explicit_tp_train_step():
    """The shard_map partial-sum stack differentiates: one explicit-TP train
    step on the (data, model) mesh matches the single-device loss and moves
    the params — with and without sequence parallelism."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan
from repro.models import model as M
from repro.optim import adamw
from repro.train import step as tstep
cfg = get_config('llama3.2-3b').reduced().replace(
    connection='fal', n_kv_heads=4)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
ocfg = adamw.AdamWConfig(lr=1e-3)
state = tstep.init_state(jax.random.PRNGKey(0), cfg, ocfg)
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                      cfg.vocab)}
l_ref, _ = M.loss_fn(state['params'], cfg, batch)
for sp in (False, True):
    plan = ExecutionPlan.from_mesh(mesh, tp='explicit', sp=sp)
    with mesh:
        step = jax.jit(tstep.make_train_step(cfg, ocfg, plan))
        new_state, metrics = step(state, batch)
    assert abs(float(metrics['loss']) - float(l_ref)) < 1e-4, sp
    assert bool(jnp.isfinite(metrics['grad_norm']))
    moved = any(float(jnp.max(jnp.abs(a - b))) > 0
                for a, b in zip(jax.tree.leaves(new_state['params']),
                                jax.tree.leaves(state['params'])))
    assert moved
print('OK')
""", timeout=900)
    assert "OK" in out


def test_sharded_moe_matches_oracle_and_grads():
    out = run_py("""
import jax, jax.numpy as jnp
from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan
from repro.models import moe as MO
cfg = get_config('qwen3-moe-30b-a3b').reduced().replace(
    n_experts=8, top_k=2, capacity_factor=8.0)
p = MO.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, cfg.d_model)) * 0.5
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
plan = ExecutionPlan.from_mesh(mesh)
y_ref, _ = MO.moe_apply(p, cfg, x)
f = jax.jit(lambda p, x: MO.moe_apply_sharded(p, cfg, x, plan))
y_sh, _ = f(p, x)
assert float(jnp.max(jnp.abs(y_sh - y_ref))) < 1e-5
# grads flow through the all_to_all dispatch
g = jax.grad(lambda p: jnp.sum(MO.moe_apply_sharded(
    p, cfg, x, plan)[0] ** 2))(p)
gr = jax.grad(lambda p: jnp.sum(MO.moe_apply(p, cfg, x)[0] ** 2))(p)
for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
    assert bool(jnp.all(jnp.isfinite(a)))
    assert float(jnp.max(jnp.abs(a - b))) < 1e-3
print('OK')
""")
    assert "OK" in out


def test_model_tp_matches_single_device():
    """Full reduced model: sharded pjit forward == single-device forward."""
    out = run_py("""
import jax, jax.numpy as jnp
from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan
from repro.launch import mesh as MX
from repro.models import model as M
cfg = get_config('llama3.2-3b').reduced().replace(connection='fal')
params = M.init_params(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
ref, _, _ = M.forward(params, cfg, {'tokens': toks})
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
plan = ExecutionPlan.from_mesh(mesh)          # implicit GSPMD
specs = MX.param_specs(params, cfg)
sh = MX.shardings_for(mesh, specs)
params_sh = jax.device_put(params, sh)
with mesh:
    y, _, _ = jax.jit(lambda p, b: M.forward(p, cfg, b, plan))(
        params_sh, {'tokens': toks})
err = float(jnp.max(jnp.abs(y - ref)))
assert err < 5e-4, err
print('OK', err)
""")
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_full_config_compiles():
    """One representative full-scale dry-run (512 host devices)."""
    out = run_py("""
from repro.launch import dryrun
info, compiled = dryrun.run_one('llama3.2-3b', 'train_4k', 'single',
                                out_dir=None)
assert 'error' not in info, info
assert compiled is not None
print('OK', info['cost']['flops'])
""", devices=512, timeout=900)
    assert "OK" in out


def test_sequence_parallel_attention_matches_auto():
    """§Perf P1: CP attention == baseline numerics (incl. gemma windows)."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan
from repro.models import model as M
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
plan = ExecutionPlan.from_mesh(mesh)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 500)
for arch in ['llama3.2-3b', 'gemma2-27b', 'deepseek-v3-671b']:
    cfg0 = get_config(arch).reduced()
    cfg1 = cfg0.replace(attn_shard='sequence')
    params = M.init_params(jax.random.PRNGKey(0), cfg0)
    b = {'tokens': toks % cfg0.vocab}
    ref, _, _ = M.forward(params, cfg0, b)
    with mesh:
        y, _, _ = jax.jit(lambda p, b: M.forward(p, cfg1, b, plan))(
            params, b)
    err = float(np.max(np.abs(np.asarray(y) - np.asarray(ref))))
    assert err < 5e-4, (arch, err)
print('OK')
""")
    assert "OK" in out


def test_shard_slot_moe_matches_oracle():
    """§Perf D3/D4: group-limited shard-slot dispatch == oracle (+grads)."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan
from repro.models import moe as MO
cfg = get_config('qwen3-moe-30b-a3b').reduced().replace(
    n_experts=8, top_k=2, capacity_factor=8.0,
    route_groups=4, route_group_limit=2)
p = MO.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, cfg.d_model)) * 0.5
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
plan = ExecutionPlan.from_mesh(mesh)
y_ref, _ = MO.moe_apply(p, cfg, x)
y_sh, _ = jax.jit(lambda p, x: MO.moe_apply_shard_slot(
    p, cfg, x, plan))(p, x)
assert float(jnp.max(jnp.abs(np.asarray(y_sh) - np.asarray(y_ref)))) < 3e-5
g = jax.grad(lambda p: jnp.sum(MO.moe_apply_shard_slot(
    p, cfg, x, plan)[0] ** 2))(p)
gr = jax.grad(lambda p: jnp.sum(MO.moe_apply(p, cfg, x)[0] ** 2))(p)
for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
    assert float(jnp.max(jnp.abs(np.asarray(a) - np.asarray(b)))) < 1e-3
print('OK')
""")
    assert "OK" in out


def test_group_limited_routing_respects_limit():
    out = run_py("""
import jax, jax.numpy as jnp
from repro.configs.base import get_config
from repro.models import moe as MO
cfg = get_config('deepseek-v3-671b').reduced().replace(
    n_experts=16, top_k=4, route_groups=4, route_group_limit=2)
p = MO.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
w, e, aux = MO._route(p, cfg, x)
for row in (e // (16 // 4)):   # group id of each chosen expert
    assert len(set(int(v) for v in row)) <= 2  # <= route_group_limit groups
print('OK')
""", devices=1)
    assert "OK" in out
