"""Helpers of the chip benchmark's CPU tests: a copy of the benchmark
tree at ``ModelConfig.reduced()`` widths, with the Pallas kernels
interpreted, driven in-process through the harness's own functions."""
import contextlib
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "bench"))

#: widths of ``ModelConfig.reduced()`` for the decoder family
REDUCED = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
               head_dim=32, d_ff=256, vocab=512, max_seq=256,
               dtype="float32", param_dtype="float32", remat=False,
               attn_block_q=32, attn_block_k=64)


def add_cell(root, name, config, traffic, chips, like):
    """A cell added as data alone: an entry in ``root``'s BENCHMARK.json,
    reporting what the cell ``like`` reports, under ``like``'s limits."""
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": chips,
                               "why": "a cell of the tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    shutil.copy(os.path.join(root, "bench", "limits", like + ".json"),
                os.path.join(root, "bench", "limits", name + ".json"))


def reduce_tree(dst, model=None):
    """Copy BENCHMARK.json and bench/ to ``dst`` and cut every
    configuration and traffic file to test size.  The four-chip training
    job, which has no cell yet, becomes the cell
    ``gpt2-774m-fal.train.tp4`` under the one-chip cell's limits."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(dst, "bench")
    for f in os.listdir(os.path.join(bench, "configs")):
        if not f.endswith(".json"):
            continue
        p = os.path.join(bench, "configs", f)
        c = json.load(open(p))
        c.update(n_layer=2, n_embd=128, n_head=4, n_inner=256,
                 vocab_size=512, n_positions=256)
        c["model"].update(REDUCED, **(model or {}))
        json.dump(c, open(p, "w"))
    for f in os.listdir(os.path.join(bench, "traffic")):
        p = os.path.join(bench, "traffic", f)
        t = json.load(open(p))
        t.update(token_ids=500, trace_seconds=1)
        if t["job"] == "train":
            t.update(seq=64, batch=4 * t["mesh"]["model"])
        else:
            t.update(rate_per_s=4,
                     prompt_len={"median": 24, "sigma": 0.8, "min": 4,
                                 "max": 60},
                     output_len={"median": 8, "sigma": 0.7, "min": 2,
                                 "max": 16},
                     max_total=128, warmup_prompt=20, check_requests=3,
                     drain_s=300, prime={"live": 2})
            t["engine"].update(page_size=8, num_pages=128, slots=4,
                               prefill_chunk=16, max_seq=128)
        json.dump(t, open(p, "w"))
    add_cell(dst, "gpt2-774m-fal.train.tp4", "gpt2-774m-fal", "train.tp4", 4,
             like="gpt2-774m-fal.train.1chip")
    return dst


@contextlib.contextmanager
def interpreted_kernels():
    """Single-device plans run the Pallas kernels in interpret mode, as
    they can on the CPU."""
    from repro.core.plan import ExecutionPlan
    orig = ExecutionPlan.__dict__["single_device"]
    ExecutionPlan.single_device = classmethod(
        lambda cls, *a, **k: orig.__func__(cls, *a, **k).with_kernels(
            "interpret"))
    try:
        yield
    finally:
        ExecutionPlan.single_device = orig


def run_cell(root, name, *, seed=2**40 + 7, seconds=2.0, trace=False,
             devices=None):
    """One run of a cell in-process, past the harness's look for a chip,
    serving cells on interpreted kernels; returns the contract's result
    line."""
    import jax
    import run as H
    cell = H.Cell(name, root=root)
    devices = devices or jax.devices()[:cell.chips]
    peaks = H.load_json(H.BENCH, "peaks.json")["TPU v5 lite"]
    kernels = (interpreted_kernels() if cell.traffic["job"] == "serve"
               else contextlib.nullcontext())
    with kernels:
        run = cell.job().run(cell, devices, seed=seed, seconds=seconds,
                             trace=trace, t_start=time.perf_counter())
    reduced, readers = None, ()
    if trace:
        import xtrace
        readers = H.readers(cell)
        reduced = xtrace.reduce(run.trace_path, [d.id for d in devices],
                                [getattr(r, "KERNEL") for _, r in readers
                                 if hasattr(r, "KERNEL")])
    return H.result_line(cell, run, devices, peaks, reduced, readers)
