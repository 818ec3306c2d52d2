"""Every cell's code path at ``ModelConfig.reduced()`` widths on the CPU,
in-process through the harness's functions (the command itself refuses a
machine without a TPU): a well-formed result line, and ``correct`` where
the program computes in float32 like the reference.  The four-chip cell
runs on four virtual CPU devices in a child process."""
import json
import os
import subprocess
import sys

import pytest

from bench_fixtures import REPO, run_cell

CONTRACT = {"correct", "attempted", "failed", "metrics", "device",
            "checks"}


def _well_formed(line, trace):
    assert set(line) == CONTRACT | ({"breakdown"} if trace else set())
    assert list(line)[-1] == "checks"
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        for key in ("device_ops", "idle_gaps"):
            assert len(line["breakdown"][key]) <= 10
    json.dumps(line, allow_nan=False)


@pytest.mark.parametrize("trace", [False, True])
def test_train_one_chip(tree, trace):
    line = run_cell(tree, "gpt2-774m-fal.train.1chip", trace=trace)
    _well_formed(line, trace)
    assert line["correct"], line["checks"]
    want = ({"mfu.train", "idle_share.train"} if trace
            else {"train_tokens_per_s", "setup_s"})
    assert want <= set(line["metrics"])


@pytest.mark.parametrize("trace", [False, True])
def test_serve_chat(tree, trace):
    line = run_cell(tree, "gpt2-1.5b-fal.serve.chat", trace=trace,
                    seconds=6.0)
    _well_formed(line, trace)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 24 and line["failed"] == 0
    if trace:
        assert {"sched.padding_share", "mfu.serve_tick"} <= set(
            line["metrics"])
    else:
        assert set(line["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                        "serve_tokens_per_s", "setup_s"}


CHILD = """
import json, sys
sys.path[:0] = [{tests!r}, {bench!r}]
from bench_fixtures import run_cell
print(json.dumps(run_cell({tree!r}, "gpt2-774m-fal.train.tp4",
                          trace={trace})))
"""


@pytest.mark.parametrize("trace", [False, True])
def test_train_tp4_on_four_cpu_devices(tree, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(tests=os.path.dirname(__file__),
                        bench=os.path.join(REPO, "bench"), tree=tree,
                        trace=trace)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    _well_formed(line, trace)
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
