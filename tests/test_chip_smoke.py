"""``chip_smoke.py`` at reduced size on the CPU: the train and serve phase
functions with the Pallas kernels interpreted, the explicit-TP phase on
four virtual CPU devices, and the refusal to run without a TPU."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_reduced(smoke, capsys):
    out = smoke.train_phase(smoke.SmokeConfig.reduced())
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) \
        == json.loads(json.dumps(out, default=float))
    for conn in ("fal", "preln"):
        rec = out[conn]
        assert rec["loss_last"] < rec["loss_first"]
        assert rec["loss_err"] <= smoke.LOSS_ATOL


def test_serve_phase_reduced_interpret(smoke):
    out = smoke.serve_phase(smoke.SmokeConfig.reduced())
    paths = out["bf16"]["dispatch_paths"]
    assert paths["paged_packed_attention"] == "interpret"
    assert paths["copy_pages"] == "interpret"
    assert out["int8"]["dispatch_paths"]["paged_packed_attention.int8"] \
        == "interpret"
    assert out["bf16"]["cow_copies"] >= 1
    assert out["bf16"]["dispatches_per_tick"] == 1.0
    # float32 at reduced size: interpreter and oracle agree far inside the
    # bf16 tolerance the chip run is held to
    assert out["tick_vs_oracle"]["max_abs_err"] < 1e-4


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_tp_phase_four_cpu_devices():
    """The --chips 4 phase on four virtual CPU devices (subprocess keeps
    this suite on one device)."""
    script = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location('chip_smoke', sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
sys.modules['chip_smoke'] = smoke
spec.loader.exec_module(smoke)
import jax
out = smoke.tp_phase(smoke.SmokeConfig.reduced(), jax.devices()[:4])
for conn in ('fal', 'preln'):
    assert out[conn]['all_reduce']['tp'] > 0, out
    assert out[conn]['all_reduce']['one_device'] == 0, out
    assert abs(out[conn]['loss_tp'] - out[conn]['loss_1']) < 1e-4, out
print('OK')
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script,
                        os.path.join(REPO, "chip_smoke.py")], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == "OK"


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """``enable_compile_cache`` (called by the entry points, here in a
    child process) uses JAX_COMPILATION_CACHE_DIR when set — compiled
    programs land there — and the fixed ``.jax_cache/`` of the checkout
    otherwise."""
    script = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path
if sys.argv[1] == 'compile':
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()
print(path)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    r = subprocess.run([sys.executable, "-c", script,
                        "compile" if from_env else "resolve"], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    path = r.stdout.strip().splitlines()[-1]
    if from_env:
        assert path == str(tmp_path / "cache")
        assert any((tmp_path / "cache").iterdir())
    else:
        assert path == os.path.join(REPO, ".jax_cache")
