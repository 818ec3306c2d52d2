"""The check that decides ``correct`` catches a broken timed path: each
fault the cell can have is planted underneath a run that skips only the
harness's look for a chip, and ``correct`` must come out false under the
cell's own limits."""
import json
import os
import subprocess
import sys

import pytest

from bench_fixtures import REPO, run_cell


def _train_step_fault(monkeypatch, fault):
    from repro.train import step as tstep
    orig = tstep.make_train_step

    def broken(*a, **k):
        step = orig(*a, **k)

        def run(state, batch):
            if fault == "unchanged":
                _, met = step(state, batch)
                return state, met
            half = batch["tokens"].shape[0] // 2
            return step(state, {"tokens": batch["tokens"][:half]})
        return run
    monkeypatch.setattr(tstep, "make_train_step", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_fault_is_not_correct(tree, monkeypatch, fault):
    _train_step_fault(monkeypatch, fault)
    line = run_cell(tree, "gpt2-774m-fal.train.1chip", seconds=1.0)
    assert not line["correct"], line["checks"]


def test_serve_altered_token_is_not_correct(tree, monkeypatch):
    """Every request's second token is replaced where it is produced."""
    from repro.serve.scheduler import PagedEngine
    orig = PagedEngine._consume_one

    def altered(self, i, tok, now):
        r = self.slots[i]
        if len(r.generated) == 1:
            tok = (tok + self.cfg.vocab // 2) % self.cfg.vocab
        return orig(self, i, tok, now)
    monkeypatch.setattr(PagedEngine, "_consume_one", altered)
    line = run_cell(tree, "gpt2-1.5b-fal.serve.chat", seconds=3.0)
    assert not line["correct"], line["checks"]


CHILD = """
import json, sys
sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
from repro.models import blocks
blocks._assemble = lambda partial, axis, compress="none": partial
from bench_fixtures import run_cell
print(json.dumps(run_cell({tree!r}, "gpt2-774m-fal.train.tp4")))
"""


def test_tp4_without_the_exchange_is_not_correct(tree):
    """The per-block all-reduce between the four chips left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(tests=os.path.dirname(__file__),
                        bench=os.path.join(REPO, "bench"),
                        src=os.path.join(REPO, "src"), tree=tree)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert not line["correct"], line["checks"]
