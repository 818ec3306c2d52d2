#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from:
the program's numbers over many seeds, and the control's and the faults'
on a few, each against the plain reference, at the cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

Runs on the chip like ``bench/run.py`` and prints one JSON line per seed,
then the summary: the largest program reading and the smallest control
and fault readings of each number, and what the harness's own comparison
(``run.compare``) makes of each reading under the cell's committed limits.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as H


KINDS = ("program", "control", "half_batch", "no_exchange")


def summary(recs):
    out = {}
    for rec in recs:
        for kind in KINDS:
            for name, v in rec.get(kind, {}).items():
                agg = max if kind == "program" else min
                key = f"{kind}.{name}"
                out[key] = v if key not in out else agg(out[key], v)
    return out


def verdicts(recs, limits):
    """``correct`` per seed for each kind of reading, as a run that read
    it would report it under ``limits``."""
    out = {}
    for rec in recs:
        for kind in KINDS:
            if kind in rec:
                ok, _ = H.compare(rec[kind], limits)
                out.setdefault(kind, []).append(ok)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = H.Cell(args.workload)
    devices = H.check_devices(cell.chips)
    H.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    recs = cell.job().calibrate(cell, devices, seeds, args.seconds)
    for rec in recs:
        print(json.dumps(rec, default=float), flush=True)
    print(json.dumps({"summary": summary(recs),
                      "correct": verdicts(recs, cell.limits)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
