"""Device time per layer scope (``bench/xscope.py``) on profiles recorded
on a TPU v5e by the benchmark and trimmed (``data/*.xplane.pb``): the
fixtures of a program without layer scopes, and those recorded with them
(``*_scoped.xplane.pb``)."""
import os
import re
import shutil

import numpy as np
import pytest

import xplane_trim
import xscope
import xtrace

DATA = os.path.join(os.path.dirname(__file__), "data")
UNSCOPED_FIXTURES = ["train_step.xplane.pb", "serve_tick.xplane.pb"]


def _trace_dir(tmp_path, name):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, name), d / "host.xplane.pb")
    return str(tmp_path)


@pytest.mark.parametrize("name", UNSCOPED_FIXTURES)
def test_a_program_without_scopes_reads_unscoped(tmp_path, name):
    """Every op of the older program is unscoped or has no stack at all,
    and the labels' times sum to the busy union ``xtrace`` reports."""
    path = _trace_dir(tmp_path, name)
    r = xscope.reduce(path, 1)
    x = xtrace.reduce(path, [0])
    assert set(r["scope_s"]) == {xscope.UNSCOPED}
    assert 0 < r["no_stack_s"] < r["busy_s"]
    assert sum(r["scope_s"].values()) == pytest.approx(x["busy_s"], abs=1e-9)
    assert r["busy_s"] == pytest.approx(x["busy_s"], abs=1e-9)
    assert r["window_s"] == pytest.approx(x["window_s"], abs=1e-9)
    assert r["host_idle_spans"] == 0 and r["ticks"] == 0


def test_backward_split_counts_transpose_stacks(tmp_path):
    """The backward share is the time of ops whose stack reads
    ``transpose(``: against the summed durations of such leaf ops (leaf
    ops run one at a time; control ops enclose them)."""
    path = _trace_dir(tmp_path, "train_step.xplane.pb")
    devices, host = xscope.load(path)
    lo, hi = next((s, e) for n, s, e in host if n == xtrace.WINDOW_SPAN)
    leaf = sum(min(e, hi) - max(s, lo)
               for name, stack, s, e in devices[0]["ops"]
               if e > lo and s < hi and "transpose(" in (stack or "")
               and xtrace.op_kind(name) not in xtrace.CONTROL) / 1e9
    back = xscope.reduce(path, 1)["backward_s"][xscope.UNSCOPED]
    assert 0 < back < xscope.reduce(path, 1)["busy_s"]
    assert back == pytest.approx(leaf, rel=1e-3)


def test_reduce_is_memoised_by_path(tmp_path):
    path = _trace_dir(tmp_path, "serve_tick.xplane.pb")
    assert xscope.reduce(path, 1) is xscope.reduce(path, 1)


def test_innermost_event_takes_each_instant():
    """A loop enclosing two body ops and a gap: each instant goes to the
    innermost op running, the loop keeps only its own time, and the split
    sums to the union."""
    ops = [("while.1", "jit(f)/attn/while", 0.0, 100.0),
           ("fusion.1", "jit(f)/attn/while/body/mul", 10.0, 40.0),
           ("fusion.2", "jit(f)/attn/kv_write/scatter", 40.0, 60.0),
           ("fusion.3", "jit(f)/mlp/dot_general", 150.0, 170.0)]
    got = xscope.exclusive(ops, 5.0, 165.0)
    assert got == {0: 5.0 + 40.0, 1: 30.0, 2: 20.0, 3: 15.0}
    assert [xscope.label(o[1]) for o in ops] == ["attn", "attn", "kv_write",
                                                 "mlp"]
    assert xscope.label(None) == xscope.label("jit(f)/while") == "unscoped"


def test_scope_table_is_the_programs():
    """The benchmark keeps its own copy of the program's layer-scope table
    and of the innermost-scope rule: a change to either in the program
    fails here, so it comes with a change to the benchmark."""
    from repro.obs import trace
    assert xscope.LAYER_SCOPES == trace.LAYER_SCOPES
    stacks = ["jit(train_step)/transpose(jvp())/while/body/closed_call/"
              "attn/dot_general",
              "jit(step)/kv_write/while/body/closed_call/attn/kv_write/"
              "scatter",
              "jit(step)/sample/vmap(jit(_uniform))/add",
              "jit(f)/jvp(assemble)/psum", "jit(f)/attn_x/mlpx/add",
              "jit(f)/while/body/dynamic_slice", ""]
    assert [xscope.innermost_scope(s) for s in stacks] == \
        [trace.innermost_scope(s) for s in stacks] == \
        ["attn", "kv_write", "sample", "assemble", None, None, None]


def test_trim_keeps_what_lies_in_the_cut(tmp_path):
    """A fixture cut to 100 ms of its window: the busy union there, and
    the ``bench.window`` span moved to the cut."""
    path = _trace_dir(tmp_path, "train_step.xplane.pb")
    devices, host = xscope.load(path)
    lo = next(s for n, s, e in host if n == xtrace.WINDOW_SPAN) + 1e8
    hi = lo + 1e8
    out = tmp_path / "cut" / "p"
    out.mkdir(parents=True)
    xplane_trim.trim(os.path.join(path, "plugins", "profile", "run",
                                  "host.xplane.pb"),
                     str(out / "cut.xplane.pb"), lo, hi)
    iv = np.asarray([(s, e) for *_, s, e in devices[0]["ops"]], float)
    want = xtrace.length(xtrace.union(xtrace.clip(iv, lo, hi))) / 1e9
    r = xtrace.reduce(str(tmp_path / "cut"), [0])
    assert r["window_s"] == pytest.approx(0.1, abs=1e-9)
    assert r["busy_s"] == pytest.approx(want, abs=1e-9)


# ------------------------------------------------- fixtures with the scopes
def _reader(name):
    import run as H
    return H.load_module(os.path.join(H.BENCH, "metrics", name + ".py"),
                         "metric_" + name.replace(".", "_"))


class _Cell:
    def __init__(self, job):
        self.traffic = {"job": job}


class _Run:
    def __init__(self, path):
        self.trace_path = path


NEW_READERS = {"train": ["attn_ms.train", "mlp_ms.train",
                         "optimizer_ms.train", "assemble_exposed_ms.train"],
               "serve": ["attn_ms.serve_tick", "kv_write_ms.serve_tick",
                         "host_idle_ms.serve_tick"]}


@pytest.mark.parametrize("name,job", [("train_step.xplane.pb", "train"),
                                      ("serve_tick.xplane.pb", "serve")])
def test_readers_of_a_program_without_scopes_read_nothing(tmp_path, name,
                                                          job):
    ctx = {"run": _Run(_trace_dir(tmp_path, name)), "chips": 1,
           "cell": _Cell(job)}
    for metric in NEW_READERS[job]:
        assert _reader(metric).read(ctx) is None, metric


def test_train_step_scopes(tmp_path):
    """300 ms of the one-chip train cell (seed 3700000011) around one
    AdamW update: the end of a backward pass, then the optimizer."""
    path = _trace_dir(tmp_path, "train_step_scoped.xplane.pb")
    r = xscope.reduce(path, 1)
    want = {"attn": 0.210057624, "embed": 0.002175437,
            "fal_signal": 0.000736004, "head": 0.005020714,
            "mlp": 0.036360714, "optimizer": 0.035706281,
            "unscoped": 0.009805177}
    assert r["scope_s"] == pytest.approx(want, abs=1e-9)
    assert sum(r["scope_s"].values()) == pytest.approx(
        xtrace.reduce(path, [0])["busy_s"], abs=1e-9)
    assert r["backward_s"]["attn"] == pytest.approx(0.183115477, abs=1e-9)
    assert r["backward_s"]["optimizer"] == 0.0
    assert r["scope_s"]["unscoped"] < 0.05 * r["busy_s"]
    assert r["steps"] == 1
    ctx = {"run": _Run(path), "chips": 1, "cell": _Cell("train")}
    got = {m: _reader(m).read(ctx) for m in NEW_READERS["train"]}
    assert got == pytest.approx({"attn_ms.train": 210.057624,
                                 "mlp_ms.train": 36.360714,
                                 "optimizer_ms.train": 35.706281,
                                 "assemble_exposed_ms.train": None})


def test_serve_tick_scopes_and_engine_phases(tmp_path):
    """One tick of the serving cell (seed 3700000131) and the start of the
    next: the packed kernel (still ``%paged_packed_attention.N`` with its
    ``name=``) under ``attn``, the layer scan's page-pool traffic under
    ``kv_write``, and the device's idle gaps named by engine phase."""
    path = _trace_dir(tmp_path, "serve_tick_scoped.xplane.pb")
    r = xscope.reduce(path, 1)
    want = {"attn": 0.756564816, "embed": 3.4953e-05,
            "fal_signal": 7.3156e-05, "head": 0.000221066,
            "kv_write": 0.129617316, "mlp": 0.00479158,
            "sample": 0.000604879, "unscoped": 0.020085564}
    assert r["scope_s"] == pytest.approx(want, abs=1e-9)
    x = xtrace.reduce(path, [0], ["paged_packed_attention"])
    assert sum(r["scope_s"].values()) == pytest.approx(x["busy_s"], abs=1e-9)
    assert r["scope_s"]["unscoped"] < 0.05 * r["busy_s"]
    assert x["kernel_calls"]["paged_packed_attention"] == 108
    assert r["ticks"] == 1 and r["host_idle_spans"] == 8
    assert r["host_idle_s"] == pytest.approx(0.017126118, abs=1e-9)
    gaps = x["breakdown"]["idle_gaps"]
    assert gaps[0][0].startswith("engine.plan")
    assert all(g.startswith("engine.") for g, _ in gaps)
    ctx = {"run": _Run(path), "chips": 1, "cell": _Cell("serve")}
    got = {m: _reader(m).read(ctx) for m in NEW_READERS["serve"]}
    assert got == pytest.approx({"attn_ms.serve_tick": 756.564816,
                                 "kv_write_ms.serve_tick": 129.617316,
                                 "host_idle_ms.serve_tick": 17.126118})


def test_serve_tick_kv_write_is_the_scatter_and_the_scans_pool_moves(
        tmp_path):
    """What ``kv_write`` counts on the chip: the scatter's own ops (stack
    ``attn/kv_write``), and from the paged layer scan only its slices
    (pools and weights), its updates of the stacked pools and the
    ``while``'s copies; no op of a layer's body."""
    path = _trace_dir(tmp_path, "serve_tick_scoped.xplane.pb")
    devices, host = xscope.load(path)
    lo, hi = next((s, e) for n, s, e in host if n == xtrace.WINDOW_SPAN)
    ops = [o for o in devices[0]["ops"] if o[3] > lo and o[2] < hi]
    split = {}
    for i, t in xscope.exclusive(ops, lo, hi).items():
        stack = ops[i][1]
        if xscope.label(stack) != "kv_write":
            continue
        key = ("scatter" if "attn/kv_write" in stack
               else [p for p in re.split(r"[/():]", stack) if p][-1])
        split[key] = split.get(key, 0.0) + t / 1e9
    assert split == pytest.approx({"scatter": 0.005388884,
                                   "dynamic_slice": 0.068396477,
                                   "dynamic_update_slice": 0.051699939,
                                   "while": 0.004132016}, abs=1e-9)
    assert sum(split.values()) == pytest.approx(
        xscope.reduce(path, 1)["scope_s"]["kv_write"], abs=1e-9)
