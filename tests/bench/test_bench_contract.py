"""BENCHMARK.json against the benchmark's contract, and the harness's
data-driven lookup: names, units, files per cell, metric coverage, a cell
added as data alone, and the command's refusal of a machine without a
TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench_fixtures import REPO, run_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))

KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert os.path.isdir(os.path.join(REPO, p))


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_allowed_keys_names_and_units(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for e in BENCH[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]


def test_each_cell_has_its_files():
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4)
        for f in (f"bench/traffic/{w['traffic']}.json",
                  f"bench/limits/{w['name']}.json"):
            assert os.path.isfile(os.path.join(REPO, f)), f
        conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
        path = os.path.join(REPO, conf["file"])
        body = json.load(open(path))
        assert body["source"] == conf["source"]
        assert sorted(body["reduced"]) == sorted(conf["reduced"])
        assert os.path.isfile(os.path.join(
            REPO, "bench", "configs", body["reference"] + ".py"))
        traffic = json.load(open(os.path.join(
            REPO, "bench", "traffic", w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(REPO, "bench", "jobs",
                                           traffic["job"] + ".py"))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(BENCH["workloads"]) // 2)


def test_setup_and_one_more_end_to_end_metric_per_cell():
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.25
    for w in BENCH["workloads"]:
        e2e = [m for m in BENCH["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert len(e2e) >= 2
        assert any(w["name"] in m.get("workloads", [])
                   for m in BENCH["per_layer"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_moves_target_is_reported_where_its_metric_is():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(REPO, "bench", "metrics",
                                           m["name"] + ".py")), m["name"]
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells)
        layers.setdefault(m["layer"], set()).add(m["name"])
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
            mfu = [p for p in BENCH["per_layer"] if "mfu" in p["name"]
                   and p["moves"] == m["moves"]]
            assert mfu, f"{m['name']} has no whole-step mfu beside it"


def test_a_cell_added_as_data_alone_runs(tmp_path):
    """A new traffic mix, its limits and a BENCHMARK.json entry, and no
    edit to a harness file: the harness finds and runs it."""
    from bench_fixtures import add_cell, reduce_tree
    root = reduce_tree(str(tmp_path))
    traffic = json.load(open(os.path.join(root, "bench", "traffic",
                                          "train.1chip.json")))
    traffic.update(batch=2, seq=32)
    json.dump(traffic, open(os.path.join(root, "bench", "traffic",
                                         "train.tiny.json"), "w"))
    add_cell(root, "gpt2-774m-fal.train.tiny", "gpt2-774m-fal", "train.tiny",
             1, like="gpt2-774m-fal.train.1chip")
    line = run_cell(root, "gpt2-774m-fal.train.tiny", seconds=1.0)
    assert line["correct"] and line["attempted"] >= 1
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gpt2-774m-fal.train.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_raises():
    import run as H
    with pytest.raises(H.BenchError):
        H.peaks_for("TPU v99")
    assert H.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
