#!/usr/bin/env python3
"""Chip benchmark of the FAL reproduction: one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); the mix's ``job`` names
the driver (``bench/jobs/<job>.py``) that sets the system up, drives it for
``--seconds`` and checks what it produced against the plain reference
beside the configuration.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` records a profiler trace of the window and reports
the per-layer metrics, each read by ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``: every number compared with its limit,
which also end standard error.  Without a TPU, or with fewer chips than
the cell asks for, the command exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
# the TPU runtime would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")


class BenchError(RuntimeError):
    """The cell cannot run here: wrong device, unknown name, bad file."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of BENCHMARK.json ``workloads`` with its files."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no cell {name!r} in BENCHMARK.json")
        self.bench, self.spec, self.name = bench, cells[name], name
        self.root, self.dir = root, os.path.join(root, "bench")
        self.chips = int(self.spec["chips"])
        conf = next(c for c in bench["configs"]
                    if c["name"] == self.spec["config"])
        self.config = load_json(root, conf["file"])
        self.traffic = load_json(self.dir, "traffic",
                                 self.spec["traffic"] + ".json")
        self.limits = load_json(self.dir, "limits", name + ".json")

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"]
                if name_in(self.name, m.get("workloads"))]

    def per_layer(self):
        moved = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]

    def trace_dir(self):
        """A fresh directory in the checkout for this cell's trace."""
        path = os.path.join(self.root, ".bench_trace", self.name)
        shutil.rmtree(path, ignore_errors=True)
        return path

    def reference(self):
        return load_module(os.path.join(self.dir, "configs",
                                        self.config["reference"] + ".py"),
                           "reference_" + self.config["reference"])

    def job(self):
        return load_module(os.path.join(self.dir, "jobs",
                                        self.traffic["job"] + ".py"),
                           "job_" + self.traffic["job"])

    def model_config(self):
        """The program's ModelConfig, built from the configuration file;
        every key the file shares with the model must agree with it."""
        from repro.configs.base import ModelConfig
        m = dict(self.config["model"])
        for key, field in self.config["same_as_model"].items():
            if m[field] != self.config[key]:
                raise BenchError(f"{self.spec['config']}: {key}="
                                 f"{self.config[key]} but model {field}="
                                 f"{m[field]}")
        return ModelConfig(arch_id=self.spec["config"], **m)


def name_in(name, workloads):
    return workloads is None or name in workloads


class Spans:
    """The benchmark's own host spans.  Under a profiler trace each span
    is also a ``TraceAnnotation``, so the trace reduction can tell what
    the host did while the device sat idle."""

    def __init__(self, annotate: bool):
        self.annotate = annotate

    def __call__(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


class Run:
    """What a job hands back to the harness."""

    def __init__(self):
        self.end_to_end = {}          # metric name -> value
        self.counters = {}            # what per-layer readers read
        self.attempted = 0
        self.failed = 0
        self.checks = {}              # number name -> value
        self.trace_path = None
        self.memory_peak_bytes = 0
        self.details = {}


class CompileCount:
    """Backend compiles (persistent-cache reads included) while active."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, secs, **_):
        if event == self.EVENT and self.on:
            self.n += 1


def enable_compile_cache():
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # every program of the cell, the Pallas ones and the quick ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def peaks_for(kind: str) -> dict:
    """The chip's published peaks (``peaks.json``); a device that is not
    in the table is an error, not a default."""
    table = load_json(BENCH, "peaks.json")
    if kind not in table:
        raise BenchError(f"no published peaks for device kind {kind!r}")
    return table[kind]


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip (0 where the backend keeps
    no statistics)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def compare(checks: dict, limits: dict):
    """Each number the cell's limits name, beside its limit; correct when
    none is over or missing."""
    out = {}
    for name, lim in limits.items():
        out[name] = {"value": checks.get(name), "limit": lim}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for v in out.values())
    return ok, out


def readers(cell: Cell):
    """(metric entry, reader module) for each per-layer metric of the
    cell."""
    return [(m, load_module(os.path.join(cell.dir, "metrics",
                                         m["name"] + ".py"),
                            "metric_" + m["name"].replace(".", "_")))
            for m in cell.per_layer()]


def result_line(cell: Cell, run: Run, devices, peaks: dict, reduced=None,
                metric_readers=()):
    """The contract's last line.  ``reduced`` (the trace reduction) is
    given for a traced run, whose metrics are the per-layer ones."""
    metrics = {}
    if reduced is not None:
        ctx = {"run": run, "trace": reduced, "peaks": peaks,
               "chips": cell.chips, "cell": cell}
        for m, reader in metric_readers:
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": run.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    ok, checks = compare(run.checks, cell.limits)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": ok, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = reduced["breakdown"]
    line["checks"] = checks
    return line


def report(line: dict, details: dict):
    """The details line, then the checks on stderr and the result line
    last on stdout."""
    print(json.dumps({"details": details}, default=float), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(args.workload)
        devices = check_devices(cell.chips)
        peaks = peaks_for(devices[0].device_kind)
    except (BenchError, OSError, KeyError, StopIteration) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    run = cell.job().run(cell, devices, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         t_start=T_START)
    reduced, metric_readers = None, ()
    if args.trace:
        import xtrace
        metric_readers = readers(cell)
        kernels = [k for _, r in metric_readers
                   for k in [getattr(r, "KERNEL", None)] if k]
        reduced = xtrace.reduce(run.trace_path, [d.id for d in devices],
                                kernels)
        run.details["trace"] = {k: reduced[k] for k in
                                ("path", "busy_s", "window_s", "kernel_s",
                                 "kernel_calls", "collective_s",
                                 "collective_exposed_s", "host_spans")}
    report(result_line(cell, run, devices, peaks, reduced, metric_readers),
           run.details)
    return 0


if __name__ == "__main__":
    sys.exit(main())
