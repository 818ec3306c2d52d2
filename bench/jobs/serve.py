"""Serving job: ``PagedEngine`` (``serve/scheduler.py``) under open-loop
arrivals from ``bench/traffic.py``, greedy sampling.

Set-up makes the weights on the device in one jitted call, builds the
engine and brings it to the steady state of the mix: the requests that
state has in flight (``traffic.prime``) take their slots and their first
tokens, and short fillers warm every other slot, so that the one
packed-tick program and every slot's first-token path are compiled and
the window opens on an engine at its working occupancy.  The window
submits each request when it is due and steps the engine while it has
work; the host clock after each ``engine.step`` stamps the tokens that
step emitted.  When the window closes, a sample of the requests that
have finished is drawn from the seed, with the one that has served the
most among them.  The engine then steps on, for up to ``drain_s``, until
every request that was due has its first token; a request without one by
then has failed.  Every token the sampled requests were served is
compared with the plain reference once the engine is freed.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

import check
import flops
import run as H
import traffic
import weights


class Work:
    """What each engine step computed, from the requests' positions
    before and after it."""

    def __init__(self, sz, page, itemsize):
        self.sz, self.page, self.itemsize = sz, page, itemsize
        self.tokens = 0
        self.flops = 0.0
        self.step_s = 0.0
        self.attn_flops = 0.0
        self.attn_bytes = 0.0

    def add(self, segs, emitted, dt, attn: bool):
        self.tokens += sum(n for _, n in segs)
        self.flops += flops.serve_tick_flops(self.sz, segs, emitted)
        self.step_s += dt
        if attn:
            f, b = flops.paged_attention_cost(self.sz, segs, self.page,
                                              self.itemsize)
            self.attn_flops += f
            self.attn_bytes += b


class Server:
    """The system under test as the window drives it: the engine over the
    benchmark's weights for a seed, in the steady state of the mix."""

    def __init__(self, cell, devices, trace: bool):
        from repro.core.plan import ExecutionPlan
        from repro.models import model as M
        from repro.serve.scheduler import EngineConfig
        self.cell, self.devices, self.trace = cell, devices, trace
        self.tr = cell.traffic
        self.cfg = cell.model_config()
        self.ref = cell.reference()
        self.sz = self.ref.sizes(cell.config)
        shapes = jax.eval_shape(lambda k: M.init_params(k, self.cfg),
                                weights.key_of(0))
        # in the type they are served in: the program's initialiser keeps
        # its matrices in float32 whatever ``param_dtype`` says
        self.make = jax.jit(weights.builder(shapes, self.cfg.n_layers,
                                            self.cfg.param_dtype))
        self.ecfg = EngineConfig(**self.tr["engine"])
        self.plan = ExecutionPlan.single_device()
        self.eng = None
        self.primed = []
        self.spans = H.Spans(trace)

    def load(self, seed: int):
        """The seed's weights (the first call builds the engine), then the
        steady state of the mix."""
        from repro.obs.trace import Tracer
        from repro.serve.scheduler import PagedEngine
        params = self.make(weights.key_of(seed))
        if self.eng is None:
            self.eng = PagedEngine(
                self.cfg, params, self.ecfg, plan=self.plan,
                tracer=Tracer() if self.trace else None)
        else:
            self.eng.params = params
        self.prime(seed)

    def prime(self, seed: int):
        """The requests in flight in the steady state take their slots and
        step until each has its first token; fillers of ``warmup_prompt``
        tokens and two new ones take every other slot, so that every slot
        index has taken the first-token path before the window."""
        from repro.serve.scheduler import ServeRequest
        eng, tr = self.eng, self.tr
        rng = np.random.default_rng([seed, 5])
        primed = [ServeRequest(rid=q.rid, prompt=q.prompt, max_new=q.max_new)
                  for q in traffic.prime(tr, seed)]
        fillers = [ServeRequest(rid=-1000 - i, max_new=2,
                                prompt=rng.integers(0, tr["token_ids"],
                                                    tr["warmup_prompt"]))
                   for i in range(self.ecfg.slots - len(primed))]
        for r in fillers + primed:
            eng.submit(r)
        eng.step()
        if eng.queue or any(s is None for s in eng.slots):
            raise H.BenchError("the page pool cannot hold the steady "
                               "state and the fillers in every slot")
        while not all(r.generated or r.done for r in primed) \
                or not all(r.done for r in fillers):
            eng.step()
        jax.block_until_ready(eng.cache)
        eng.finished.clear()
        self.primed = [r for r in primed if not r.done]

    def drive(self, seed: int, seconds: float, trace_path=None) -> dict:
        """The seed's requests, each submitted when due, for ``seconds``;
        then the check's sample is drawn and the engine steps on, for up
        to ``drain_s``, until every request due has its first token."""
        from repro.serve.scheduler import ServeRequest
        eng, tr, spans = self.eng, self.tr, self.spans
        eng.reset_stats()
        reqs = traffic.schedule(tr, seed, seconds)
        itemsize = 2 if self.ecfg.kv_dtype in ("", "bf16") else 1
        work = Work(self.sz, self.ecfg.page_size, itemsize)
        compiles = H.CompileCount()
        live = {r.rid: r for r in self.primed}  # rid -> ServeRequest
        made = dict(live)                       # every request served
        times = {rid: [] for rid in list(live) + [r.rid for r in reqs]}
        at_start = {"live": sum(s is not None for s in eng.slots),
                    "pages": eng.allocator.in_use}
        if trace_path:
            jax.profiler.start_trace(trace_path)
        compiles.on = True
        t0 = time.perf_counter()
        t_end = t0 + seconds
        win = spans("bench.window")
        win.__enter__()
        traced = bool(trace_path)
        nxt = 0
        late = []
        backlog = []                            # (time, queued requests)
        occupancy = []                          # (live slots, pages)

        def step(in_window):
            """One engine step; stamps the tokens it emitted and counts
            the segments it computed: (first position, tokens)."""
            in_slot = {id(r) for r in eng.slots if r is not None}
            snap = [(r, r.pos, len(r.generated), id(r) in in_slot)
                    for r in live.values()]
            s = time.perf_counter()
            with spans("bench.step"):
                eng.step()
            now = time.perf_counter()
            segs, emitted = [], 0
            for r, p0, g0, was_in in snap:
                k = len(r.generated) - g0
                times[r.rid].extend([now] * k)
                emitted += k
                if not was_in and r.pos:         # admitted by this step
                    p0 = max(p0, min(r.prefix_hit_tokens,
                                     len(r.known()) - 1))
                if r.pos > p0:
                    segs.append((p0, r.pos - p0))
                if r.done:
                    del live[r.rid]
            if in_window:
                work.add(segs, emitted, now - s, traced)
                backlog.append((now - t0, len(eng.queue)))
                occupancy.append((sum(x is not None for x in eng.slots),
                                  eng.allocator.in_use))

        def submit_due(now):
            nonlocal nxt
            with spans("bench.submit"):
                while nxt < len(reqs) and t0 + reqs[nxt].at_s <= now:
                    q = reqs[nxt]
                    r = ServeRequest(rid=q.rid, prompt=q.prompt,
                                     max_new=q.max_new)
                    eng.submit(r)
                    live[q.rid] = made[q.rid] = r
                    late.append(now - (t0 + q.at_s))
                    nxt += 1

        def stop_trace():
            jax.block_until_ready(eng.cache)
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()

        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            submit_due(now)
            if eng.queue or any(s is not None for s in eng.slots):
                step(True)
            else:
                due = t0 + reqs[nxt].at_s if nxt < len(reqs) else t_end
                with spans("bench.wait"):
                    time.sleep(max(0.0, min(due, t_end)
                                   - time.perf_counter()))
            if traced and time.perf_counter() - t0 >= tr["trace_seconds"]:
                stop_trace()
                traced = False
        stats = eng.stats()
        if traced:
            stop_trace()
        # every request was due inside the window: the ones a long last
        # step kept from their submission go in now, late by that step
        submit_due(time.perf_counter())
        served = [r for r in made.values() if r.generated]
        pick = rng_pick([r for r in served if r.done and not r.truncated]
                        or served, seed, tr["check_requests"])
        drain_end = time.perf_counter() + tr["drain_s"]
        while ((any(not times[q.rid] for q in reqs)
                or not all(r.done for r in pick))
               and time.perf_counter() < drain_end):
            step(False)
        compiles.on = False
        eng.finished.clear()
        return {"reqs": reqs, "times": times, "made": made, "t0": t0,
                "sample": [(np.asarray(r.prompt), np.asarray(r.generated))
                           for r in pick],
                "t_end": t_end, "drain_end": drain_end, "late": late,
                "stats": stats, "work": work, "compiles": compiles.n,
                "backlog": backlog, "at_start": at_start,
                "occupancy": occupancy}

    def settle(self):
        """Let the engine run dry before another seed or rate: queued
        requests are dropped and those in a slot stop at their next
        token."""
        eng = self.eng
        eng.queue.clear()
        for r in eng.slots:
            if r is not None:
                r.max_new = len(r.generated) + 1
        eng.run()
        eng.finished.clear()

    def free(self):
        """Drop the engine and its page pools before the reference runs."""
        self.eng.cache = None
        self.eng = None
        gc.collect()


def run(cell, devices, *, seed, seconds, trace, t_start):
    out = H.Run()
    srv = Server(cell, devices, trace)
    srv.load(seed)
    trace_path = cell.trace_dir() if trace else None
    res = srv.drive(seed, seconds, trace_path)
    t0, t_end = res["t0"], res["t_end"]
    ttft, itl, emitted_in_window, failed = [], [], 0, 0
    for q in res["reqs"]:
        ts = res["times"][q.rid]
        r = res["made"].get(q.rid)
        failed += not ts or (r is not None and r.truncated)
        due = t0 + q.at_s
        ttft.append((ts[0] - due) if ts else (res["drain_end"] - due))
    for ts in res["times"].values():
        itl.extend(b - a for a, b in zip(ts, ts[1:]) if b <= t_end)
        emitted_in_window += sum(1 for t in ts if t <= t_end)
    out.end_to_end = {
        "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
        "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3 if itl else None,
        "serve_tokens_per_s": emitted_in_window / seconds,
        "setup_s": t0 - t_start}
    out.attempted, out.failed = len(res["reqs"]), failed
    stats, work, late = res["stats"], res["work"], res["late"]
    occ = np.asarray(res["occupancy"] or [(0, 0)])
    out.counters = {"padding_share": stats["padding_fraction"]["mean"],
                    "model_flops": work.flops, "step_s": work.step_s,
                    "attn_flops": work.attn_flops,
                    "attn_bytes": work.attn_bytes}
    out.memory_peak_bytes = H.peak_bytes(devices)
    out.trace_path = trace_path
    out.details = {
        "cell": cell.name, "seed": seed, "requests": len(res["reqs"]),
        "rate_per_s": cell.traffic["rate_per_s"],
        "engine": cell.traffic["engine"],
        "compiles_in_window": res["compiles"],
        "submit_late_ms": {"mean": float(np.mean(late)) * 1e3,
                           "max": float(np.max(late)) * 1e3},
        "ticks": stats["ticks"], "live_tokens": work.tokens,
        "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
        "itl_p50_ms": float(np.percentile(itl, 50)) * 1e3 if itl else None,
        "itl_samples": len(itl), "preemptions": stats["preemptions"],
        "in_flight_at_start": res["at_start"],
        "live_slots": {"mean": float(np.mean(occ[:, 0])),
                       "min": int(occ[:, 0].min()),
                       "max": int(occ[:, 0].max())},
        "pages_in_use": {"mean": float(np.mean(occ[:, 1])),
                         "max": int(occ[:, 1].max())}}
    seqs = res["sample"]
    params = srv.eng.params
    srv.free()
    t_ref = time.perf_counter()
    gaps = srv.ref.token_gaps(params, seqs, srv.sz)
    out.checks = check.serve_numbers(gaps) if gaps else {"token_gap": None}
    out.details["reference_s"] = time.perf_counter() - t_ref
    out.details["checked_tokens"] = int(sum(len(s) for _, s in seqs))
    return out


def calibrate(cell, devices, seeds, seconds):
    """Readings that set the limit: per seed, the program's widest token
    gap, and the control's (the reference in float8 e4m3 matmuls, judged
    by the tokens it puts first) on the same prompts and tokens."""
    srv = Server(cell, devices, False)
    runs = []
    for seed in seeds:
        srv.load(seed)
        res = srv.drive(seed, seconds)
        runs.append((seed, res["sample"]))
        srv.settle()
    srv.free()
    out = []
    for seed, seqs in runs:
        if not seqs:
            out.append({"seed": seed, "tokens": 0})
            continue
        params = srv.make(weights.key_of(seed))
        prog = check.serve_numbers(srv.ref.token_gaps(params, seqs, srv.sz))
        ctl = check.serve_numbers(srv.ref.token_gaps(
            params, seqs, srv.sz, prec="fp8", pick="own"))
        out.append({"seed": seed, "tokens": int(sum(len(s) for _, s in seqs)),
                    "program": prog, "control": ctl})
    return out


def rng_pick(served, seed, k):
    """The request that has served the most tokens and ``k - 1`` others
    drawn from the seed."""
    if not served:
        return []
    longest = max(served, key=lambda r: len(r.generated))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng([seed, 9])
    idx = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(idx)]
