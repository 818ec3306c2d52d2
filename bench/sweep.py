#!/usr/bin/env python3
"""The sweep that fixes a serving cell's offered rate: one engine, the
cell's traffic at each rate in turn, and for each the work completed in
the window, the tails and the backlog of requests waiting for a slot.

    python3 bench/sweep.py --workload <cell> --rates 0.2,0.4 --seconds 40

Each window starts from an idle engine.  Above the knee the slots fill
and the second half of the window runs at the engine's capacity in
tokens/s; that capacity over the mix's mean output length is the knee in
requests/s, and the cell's traffic file offers about four fifths of it.  Run on
the chip once, when a cell is added; the benchmark's runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as H


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = H.Cell(args.workload)
    devices = H.check_devices(cell.chips)
    H.enable_compile_cache()
    job = cell.job()
    srv = job.Server(cell, devices, False)
    idle = {k: v for k, v in cell.traffic.items() if k != "prime"}
    srv.tr = idle
    srv.load(args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        srv.tr = dict(idle, rate_per_s=rate, drain_s=0.0)
        res = srv.drive(args.seed, args.seconds)
        t_end = res["t_end"]
        ttft = [ts[0] - (res["t0"] + q.at_s) for q in res["reqs"]
                for ts in [res["times"][q.rid]] if ts and ts[0] <= t_end]
        itl = [b - a for ts in res["times"].values()
               for a, b in zip(ts, ts[1:]) if b <= t_end]
        toks = sum(1 for ts in res["times"].values() for t in ts
                   if t <= t_end)
        mid = res["t0"] + args.seconds / 2
        toks_late = sum(1 for ts in res["times"].values() for t in ts
                        if mid < t <= t_end)
        half = [n for t, n in res["backlog"] if t < args.seconds / 2]
        late = [n for t, n in res["backlog"] if t >= args.seconds / 2]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(res["reqs"]),
            "first_tokens": len(ttft),
            "tokens_per_s": toks / args.seconds,
            "tokens_per_s_second_half": toks_late / (args.seconds / 2),
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else None,
            "itl_p50_s": float(np.percentile(itl, 50)) if itl else None,
            "itl_p95_s": float(np.percentile(itl, 95)) if itl else None,
            "backlog_first_half": float(np.mean(half)) if half else 0.0,
            "backlog_second_half": float(np.mean(late)) if late else 0.0,
            "backlog_end": res["backlog"][-1][1] if res["backlog"] else 0,
            "ticks": res["stats"]["ticks"]}), flush=True)
        srv.settle()
    return 0


if __name__ == "__main__":
    sys.exit(main())
