"""The control at a size a test run can hold: the plain reference computed
with float8 e4m3 matmuls, one step below the configuration's bfloat16, in
the program's place.  At ``ModelConfig.reduced()`` widths with the
program in bfloat16 as the configuration states, the control's numbers
must stand well clear of the program's on every seed, on one of the
numbers the check compares (``bench/check.py``): the first gradient's
worst and mean leaf, and the median leaf's change.  Passed through the harness's
own comparison under the committed limits, the control and the
half-batch fault read as not correct on every seed, and the program, in
float32 as the reference computes, as correct.
On the chip the same readings, at the cells' own sizes, set the limits
(``bench/calibrate.py``)."""
import jax
import pytest

from bench_fixtures import reduce_tree
import calibrate
import run as H

SEEDS = [1, 2**40 + 3, 5]


@pytest.fixture(scope="module")
def bf16_tree(tmp_path_factory):
    return reduce_tree(str(tmp_path_factory.mktemp("bf16_tree")),
                       model={"dtype": "bfloat16"})


def test_train_control_separates_from_the_program(bf16_tree):
    cell = H.Cell("gpt2-774m-fal.train.1chip", root=bf16_tree)
    recs = cell.job().calibrate(cell, jax.devices()[:1], SEEDS, 1.0)
    s = calibrate.summary(recs)
    # the widest program reading against the narrowest control reading
    assert s["control.grad1_gap"] > 3 * s["program.grad1_gap"], s
    for name in ("grad1_gap_mean", "change_gap_median"):
        assert s["control." + name] > 3 * s["program." + name], s
    # the half-batch fault is caught by the gradient and the change
    assert s["half_batch.grad1_gap"] > 10 * s["program.grad1_gap"], s
    assert s["half_batch.change_gap"] > 10 * s["program.change_gap"], s
    v = calibrate.verdicts(recs, cell.limits)
    assert not any(v["control"]) and not any(v["half_batch"]), v


def test_train_control_is_not_correct_under_the_limits(tree):
    cell = H.Cell("gpt2-774m-fal.train.1chip", root=tree)
    recs = cell.job().calibrate(cell, jax.devices()[:1], SEEDS[:2], 1.0)
    v = calibrate.verdicts(recs, cell.limits)
    assert all(v["program"]), v
    assert v["control"] == [False, False], v
    assert v["half_batch"] == [False, False], v
