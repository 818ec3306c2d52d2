"""The trace reduction on profiles recorded on a TPU v5e by the benchmark
itself and trimmed to a few hundred milliseconds of the ``bench.window``
span (``data/*.xplane.pb``)."""
import os
import shutil

import numpy as np
import pytest

import xtrace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _trace_dir(tmp_path, name):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, name), d / "host.xplane.pb")
    return str(tmp_path)


def test_train_step_trace(tmp_path):
    """One step of the one-chip train cell (seed 1300000057)."""
    path = _trace_dir(tmp_path, "train_step.xplane.pb")
    r = xtrace.reduce(path, [0])
    assert r["window_s"] == pytest.approx(0.3)
    assert r["busy_s"] == pytest.approx(0.286371792, abs=1e-9)
    assert r["idle_share"] == pytest.approx(1 - 0.286371792 / 0.3)
    assert r["collective_s"] == 0.0
    assert r["host_spans"]["bench.step"] == 2
    ops = r["breakdown"]["device_ops"]
    assert len(ops) == 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0].startswith("bench.feed")
    assert sum(v for _, v in gaps) == pytest.approx(0.3 - r["busy_s"])


def test_busy_union_against_a_brute_force_count(tmp_path):
    """Busy time as the union of the op intervals, against marking every
    microsecond the ops cover."""
    path = _trace_dir(tmp_path, "train_step.xplane.pb")
    devices, host = xtrace.load(path)
    lo, hi = next((s, e) for n, s, e in host if n == xtrace.WINDOW_SPAN)
    covered = np.zeros(int((hi - lo) / 1e3) + 1, bool)
    for _, _, s, e in devices[0]["ops"]:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            covered[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
    brute = covered.sum() * 1e3 / 1e9
    assert xtrace.reduce(path, [0])["busy_s"] == pytest.approx(brute,
                                                                abs=2e-3)


def test_serve_tick_trace_finds_the_kernel(tmp_path):
    """700 ms of the serving cell (seed 1100000009): the packed paged
    attention kernel, by the name the trace prints for its custom call,
    once per layer per tick."""
    path = _trace_dir(tmp_path, "serve_tick.xplane.pb")
    r = xtrace.reduce(path, [0], ["paged_packed_attention"])
    assert r["kernel_calls"]["paged_packed_attention"] == 96
    assert r["kernel_s"]["paged_packed_attention"] == pytest.approx(
        0.523146283, abs=1e-9)
    assert r["kernel_s"]["paged_packed_attention"] <= r["busy_s"]
    top = r["breakdown"]["device_ops"][0][0]
    assert top.startswith("paged_packed_attention.")
    assert r["breakdown"]["idle_gaps"][0][0].startswith("engine.dispatch")
