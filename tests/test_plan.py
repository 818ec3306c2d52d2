"""ExecutionPlan (core/plan.py): construction, validation errors,
rejection of the expired legacy-dict shim, and SP-vs-replicated logits
equivalence.

Validation unit tests use a lightweight fake mesh (validate only reads
``axis_names``/``shape``); the equivalence test spawns a subprocess with 2
forced CPU host devices so the rest of the suite keeps seeing 1 device.
"""
import os
import subprocess
import sys
import types

import pytest

from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan, Phase, TPStyle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fake_mesh(**axes):
    return types.SimpleNamespace(shape=dict(axes),
                                 axis_names=tuple(axes))


def cfg_for(arch="llama3.2-3b", **kw):
    return get_config(arch).reduced().replace(**kw)


# ------------------------------------------------------------------ build --
def test_single_device_defaults():
    p = ExecutionPlan.single_device()
    assert p.phase is Phase.TRAIN and p.tp is TPStyle.NONE
    assert p.tp_size == 1 and p.tp_axis is None
    assert not p.use_explicit_tp and not p.is_sharded
    p.validate(cfg_for())      # nothing to reject


@pytest.mark.parametrize("kernels", ["auto", "pallas", "interpret", "ref"])
def test_kernels_paths_map_to_dispatcher_args(kernels):
    from repro.kernels import ops
    p = ExecutionPlan.single_device().with_kernels(kernels)
    assert p.validate(cfg_for()).kernels == kernels
    args = ops.kernel_args(kernels)
    assert set(args) <= {"use_pallas", "interpret"}


def test_unknown_kernels_path_rejected():
    with pytest.raises(ValueError, match="unknown kernels"):
        ExecutionPlan.single_device().with_kernels("mosaic").validate(
            cfg_for())


def test_from_mesh_axes_and_styles():
    mesh = fake_mesh(pod=2, data=4, model=8)
    p = ExecutionPlan.from_mesh(mesh, tp="explicit")
    assert p.data_axes == ("pod", "data") and p.model_axis == "model"
    assert p.tp is TPStyle.EXPLICIT and p.tp_size == 8
    assert p.use_explicit_tp
    # tp_axis only exists INSIDE the shard_map body
    assert p.tp_axis is None
    inner = p.inner()
    assert inner.mesh is None and inner.tp_axis == "model"
    assert inner.tp_size == 8


def test_phase_coercion_and_unknown_phase():
    assert Phase.coerce("train") is Phase.TRAIN
    assert Phase.coerce(Phase.DECODE) is Phase.DECODE
    with pytest.raises(ValueError, match="unknown phase"):
        Phase.coerce("warmup")
    with pytest.raises(ValueError, match="unknown phase"):
        ExecutionPlan.resolve("warmup")
    with pytest.raises(ValueError, match="unknown TP style"):
        TPStyle.coerce("megatron")


def test_with_phase_is_pure():
    p = ExecutionPlan.single_device()
    q = p.with_phase("decode")
    assert q.phase is Phase.DECODE and p.phase is Phase.TRAIN
    assert not q.full_sequence and p.full_sequence


# --------------------------------------------------------------- validate --
def test_validate_bad_divisibility():
    mesh = fake_mesh(model=8)
    plan = ExecutionPlan.from_mesh(mesh, tp="explicit")
    with pytest.raises(ValueError, match="n_heads=6 is not divisible"):
        plan.validate(cfg_for(n_heads=6, n_kv_heads=6))
    with pytest.raises(ValueError, match="n_kv_heads=3 divides neither"):
        plan.validate(cfg_for(n_heads=8, n_kv_heads=3))
    with pytest.raises(ValueError, match="d_ff=100"):
        plan.validate(cfg_for(n_heads=8, n_kv_heads=8, d_ff=100))


def test_validate_family_and_mesh():
    mesh = fake_mesh(data=2, model=4)
    with pytest.raises(ValueError, match="no.*explicit-TP stack"):
        ExecutionPlan.from_mesh(mesh, tp="explicit").validate(
            cfg_for("mamba2-370m"))
    with pytest.raises(ValueError, match="requires a mesh"):
        ExecutionPlan(tp=TPStyle.EXPLICIT).validate(cfg_for())
    with pytest.raises(ValueError, match="model_axis 'tp' not in"):
        ExecutionPlan.from_mesh(mesh, tp="explicit",
                                model_axis="tp").validate(cfg_for())


def test_validate_sp_needs_explicit_tp_and_full_sequence():
    mesh = fake_mesh(model=4)
    with pytest.raises(ValueError, match="requires tp='explicit'"):
        ExecutionPlan.from_mesh(mesh, tp="gspmd", sp=True).validate(cfg_for())
    with pytest.raises(ValueError, match="full-sequence"):
        ExecutionPlan.from_mesh(mesh, tp="explicit", sp=True,
                                phase="decode").validate(cfg_for())
    # the supported combination passes
    ExecutionPlan.from_mesh(mesh, tp="explicit", sp=True).validate(
        cfg_for(n_kv_heads=4))


# ---------------------------------------------------- expired legacy shim --
def test_resolve_rejects_context_dicts():
    """The one-release legacy parallel-ctx dict shim has expired: resolve()
    must fail loudly on a dict (pointing at the replacement), never
    silently coerce it."""
    mesh = fake_mesh(data=2, model=4)
    legacy = {"mesh": mesh, "data_axes": ("data",), "model_axis": "model",
              "tp": "explicit"}
    with pytest.raises(TypeError, match="no longer accepted"):
        ExecutionPlan.resolve(legacy)
    with pytest.raises(TypeError, match="no longer accepted"):
        ExecutionPlan.resolve({})
    assert not hasattr(ExecutionPlan, "from_legacy_dict")
    assert not hasattr(ExecutionPlan, "to_legacy_dict")


# ------------------------------------------- SP == replicated (2 devices) --
def test_sp_logits_match_replicated_two_device_mesh():
    """SP-vs-replicated logits equivalence for preln/fal/falplus on a
    2-device CPU mesh (subprocess keeps the main suite single-device)."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan
from repro.models import model as M
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ('model',))
toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 500)
for mode in ('preln', 'fal', 'falplus'):
    cfg = get_config('llama3.2-3b').reduced().replace(connection=mode)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    b = {'tokens': toks % cfg.vocab}
    ref, _, _ = M.forward(params, cfg, b)
    plan = ExecutionPlan.from_mesh(mesh, tp='explicit', sp=True).validate(cfg)
    with mesh:
        y, _, _ = jax.jit(lambda p, b: M.forward(p, cfg, b, plan))(params, b)
    err = float(np.max(np.abs(np.asarray(y) - np.asarray(ref))))
    assert err < 5e-4, (mode, err)
print('OK')
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
