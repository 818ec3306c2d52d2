"""Training job: the program's jitted train step (``train/step.py``) on a
``data x model`` mesh, fed synthetic Markov tokens made from the seed.

Set-up makes the weights and AdamW state on the device in one jitted call,
compiles the step, and drives that same step through its first
``check_steps`` steps, which are also the warm-up: their losses, the first
clipped gradient (read back from the first moment) and the parameters'
change are kept for the check.  The window then runs the same step on
fresh rows for ``seconds``, at most ``AHEAD`` steps ahead of the device, and
ends on ``block_until_ready``.  Once the window has closed and the state
is freed, the plain reference repeats the check steps on one chip.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import run as H
import weights


#: steps the host may run ahead of the device in the window: about six
#: seconds of steps at the cell's ~0.95 s a step, so the device stays fed
#: through a host stall of some seconds, and the last wait stays short
AHEAD = 6


class Markov:
    """Token rows of a sparse random Markov chain over the first ``vocab``
    ids: every id has ``branching`` successors with random weights.  Row
    ``r`` of step ``i`` is a pure function of (seed, i, r)."""

    def __init__(self, seed: int, vocab: int, seq: int, batch: int,
                 branching: int):
        rng = np.random.default_rng([seed, 7])
        self.succ = rng.integers(0, vocab, (vocab, branching), np.int32)
        p = rng.random((vocab, branching)) + 0.1
        self.cum = np.cumsum(p / p.sum(1, keepdims=True), 1)
        self.seed, self.vocab, self.seq, self.batch = seed, vocab, seq, batch

    def batch_at(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 11, i])
        toks = np.empty((self.batch, self.seq), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, self.batch)
        u = rng.random((self.batch, self.seq))
        for t in range(1, self.seq):
            prev = toks[:, t - 1]
            j = (u[:, t:t + 1] > self.cum[prev]).sum(1)
            toks[:, t] = self.succ[prev, np.minimum(j, self.succ.shape[1] - 1)]
        return toks


class Trainer:
    """The system under test as the window drives it: compiled step,
    state, feed and shardings on the cell's mesh."""

    def __init__(self, cell, devices, seed: int):
        from jax.sharding import NamedSharding
        from repro.core.plan import ExecutionPlan
        from repro.launch import mesh as MX
        from repro.optim import adamw
        from repro.train import step as tstep

        tr = cell.traffic
        self.cfg = cfg = cell.model_config()
        self.opt = tr["optimizer"]
        self.ocfg = adamw.AdamWConfig(**self.opt)
        self.batch, self.seq = tr["batch"], tr["seq"]
        mesh = MX.make_mesh((tr["mesh"]["data"], tr["mesh"]["model"]),
                            ("data", "model"), devices=devices)
        plan = (ExecutionPlan.from_mesh(mesh, tp=tr["tp"]).validate(cfg)
                if len(devices) > 1 else ExecutionPlan.single_device())
        shapes = jax.eval_shape(
            lambda k: tstep.init_state(k, cfg, self.ocfg), weights.key_of(0))
        self.st_sh = MX.shardings_for(mesh, MX.state_specs(shapes, cfg))
        self.b_sh = NamedSharding(mesh, MX.batch_spec(mesh))
        make = weights.builder(shapes["params"], cfg.n_layers)
        self.make_params = jax.jit(make, out_shardings=self.st_sh["params"])
        self.init = jax.jit(
            lambda k: (lambda p: {"params": p, "opt": adamw.init_opt_state(
                p, self.ocfg)})(make(k)), out_shardings=self.st_sh)
        self.step = jax.jit(tstep.make_train_step(cfg, self.ocfg, plan),
                            in_shardings=(self.st_sh, {"tokens": self.b_sh}),
                            out_shardings=(self.st_sh, None),
                            donate_argnums=(0,))
        self.tr = tr
        self.reseed(seed)
        b1 = self.opt["b1"]
        self.grad1 = jax.jit(lambda m: check.leaf_norms(
            jax.tree.map(lambda x: x / (1 - b1), m)))
        self.change = jax.jit(lambda p, k: check.leaf_norms(
            jax.tree.map(lambda a, b: a.astype(jnp.float32)
                         - b.astype(jnp.float32), p, make(k))))
        self.state = None

    def reseed(self, seed: int):
        """Weights and rows of another seed, through the same programs."""
        self.key = weights.key_of(seed)
        self.feed_src = Markov(seed, self.tr["token_ids"], self.seq,
                               self.batch, self.tr["markov_branching"])

    def feed(self, i: int):
        return {"tokens": jax.device_put(self.feed_src.batch_at(i),
                                         self.b_sh)}

    def check_steps(self, n: int) -> dict:
        """Steps 1..n from fresh weights, through the window's own call
        and feed; the readings the check compares."""
        self.state = self.init(self.key)
        losses, g1 = [], None
        for i in range(1, n + 1):
            self.state, met = self.step(self.state, self.feed(i))
            losses.append(float(met["loss"]))
            if i == 1:
                g1 = np.asarray(self.grad1(self.state["opt"]["m"]))
        dp = np.asarray(self.change(self.state["params"], self.key))
        return {"loss": losses, "grad1": g1, "change": dp}


def run(cell, devices, *, seed, seconds, trace, t_start):
    tr = cell.traffic
    out = H.Run()
    spans = H.Spans(trace)
    compiles = H.CompileCount()
    t = Trainer(cell, devices, seed)
    n_check = tr["check_steps"]
    prog = t.check_steps(n_check)

    trace_path = None
    if trace:
        trace_path = cell.trace_dir()
        jax.profiler.start_trace(trace_path)
    jax.block_until_ready(t.state)
    compiles.on = True
    # ------------------------------------------------------------ window
    t0 = time.perf_counter()
    n, pending, traced, done = 0, [], trace, []
    win = spans("bench.window")
    win.__enter__()
    while time.perf_counter() - t0 < seconds:
        with spans("bench.feed"):
            b = t.feed(n_check + 1 + n)
        with spans("bench.step"):
            t.state, met = t.step(t.state, b)
        n += 1
        pending.append(met)
        if len(pending) >= AHEAD:
            with spans("bench.wait"):
                jax.block_until_ready(pending.pop(0))
            done.append(time.perf_counter())
        if traced and time.perf_counter() - t0 >= tr["trace_seconds"]:
            with spans("bench.wait"):
                jax.block_until_ready(t.state)
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced = False
    jax.block_until_ready(t.state)
    t1 = time.perf_counter()
    if traced:
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles.on = False
    # ------------------------------------------------------------ after
    tokens = n * t.batch * t.seq
    out.end_to_end = {"train_tokens_per_s": tokens / (t1 - t0),
                      "setup_s": t0 - t_start}
    out.counters = {"batch": t.batch, "seq": t.seq}
    out.attempted = n
    out.memory_peak_bytes = H.peak_bytes(devices)
    out.trace_path = trace_path
    out.details = {"cell": cell.name, "seed": seed, "steps": n,
                   "batch": t.batch, "seq": t.seq,
                   "compiles_in_window": compiles.n,
                   "step_gap_s": {"median": float(np.median(np.diff(done))),
                                  "max": float(np.max(np.diff(done)))}
                   if len(done) > 2 else None,
                   "check_losses": prog["loss"]}
    del met, pending, b
    t.state = None

    t_ref = time.perf_counter()
    ref = reference_readings(cell, t, devices, n_check)
    out.checks = check.train_numbers(prog, ref)
    out.details["reference_s"] = time.perf_counter() - t_ref
    out.details["readings"] = check.train_detail(prog, ref)
    return out


def reference_readings(cell, t, devices, n_check, prec="f32", rows=None):
    """The plain reference's readings over the check steps of ``t``'s
    seed, its rows spread over the cell's chips; ``rows`` keeps only the
    first rows of each batch."""
    ref_mod = cell.reference()
    one = jax.jit(weights.builder(jax.eval_shape(t.make_params, t.key),
                                  t.cfg.n_layers),
                  out_shardings=jax.sharding.SingleDeviceSharding(
                      devices[0]))
    batches = [t.feed_src.batch_at(i)[:rows]
               for i in range(1, n_check + 1)]
    return ref_mod.train_readings(lambda: one(t.key), batches,
                                  ref_mod.sizes(cell.config), t.opt, prec,
                                  devices=list(devices))


def calibrate(cell, devices, seeds, seconds):
    """Readings that set the limits, per seed: the program's numbers; the
    control's (the reference in float8 e4m3 matmuls in the program's
    place) and the half-batch fault's (the reference on the first half of
    each batch) on three of the seeds; on several chips also the
    program's with the exchange between chips left out (every
    ``blocks._assemble`` returning its local partial sum), on the same
    three.  All against the float32 reference."""
    t = Trainer(cell, devices, seeds[0])
    n = cell.traffic["check_steps"]
    out, refs = [], {}
    for i, seed in enumerate(seeds):
        t.reseed(seed)
        prog = t.check_steps(n)
        t.state = None
        ref = refs[seed] = reference_readings(cell, t, devices, n)
        rec = {"seed": seed, "program": check.train_numbers(prog, ref),
               "detail": check.train_detail(prog, ref)}
        if i < 3:
            ctl = reference_readings(cell, t, devices, n, "fp8")
            half = reference_readings(cell, t, devices, n,
                                      rows=t.batch // 2)
            rec["control"] = check.train_numbers(ctl, ref)
            rec["half_batch"] = check.train_numbers(half, ref)
            rec["control_detail"] = check.train_detail(ctl, ref)
        out.append(rec)
    if len(devices) > 1:
        from repro.models import blocks
        blocks._assemble = lambda partial, axis, compress="none": partial
        t = Trainer(cell, devices, seeds[0])
        for rec in out[:3]:
            t.reseed(rec["seed"])
            rec["no_exchange"] = check.train_numbers(t.check_steps(n),
                                                     refs[rec["seed"]])
            t.state = None
    return out
