#!/usr/bin/env python3
"""Bring-up smoke run on the TPU: the FAL train step and the packed serving
tick at ``gpt2-117m``'s published width on one chip, and the explicit
tensor-parallel train step on four.

    python chip_smoke.py              # train + serve phases, one chip
    python chip_smoke.py --chips 4    # explicit-TP train step only, 4 chips

Weights are random, made from ``--seed``.  Every phase checks its result
against a reference computed on the same chip and prints one JSON line; a
failed check raises, and the script exits non-zero.  The last line of
standard output is the device record
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
There is no CPU fallback: without a TPU the script exits non-zero before
any phase runs.  The phase functions take a ``SmokeConfig``, so the tests
run them at reduced size on the CPU with the Pallas interpreter.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core import tp as TP  # noqa: E402
from repro.core.plan import ExecutionPlan  # noqa: E402
from repro.data.pipeline import SyntheticMarkov  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import mesh as MX  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.attention import INT8_LOGIT_RTOL  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serve.paged_cache import pages_needed  # noqa: E402
from repro.serve.scheduler import (EngineConfig, PagedEngine,  # noqa: E402
                                   ServeRequest, make_packed_step,
                                   pack_tokens)
from repro.train import step as tstep  # noqa: E402
from repro.train import trainer  # noqa: E402

#: step-0 loss of the bf16 train step against the float32 reference
#: forward on the same params and batch.  bf16 keeps 8 significant bits
#: (relative rounding <= 2^-9 per operation); the loss averages over
#: batch x seq tokens, so rounding that does not bias the logits stays far
#: inside 0.05 nats of a ~10.8-nat initial loss (0.5%).
LOSS_ATOL = 0.05
#: one packed tick on the Pallas kernels against the same tick on the
#: gather oracle: both accumulate attention in float32 and differ only in
#: summation order; the bf16 cast of each layer's attention output turns
#: that into at most one bf16 ulp (2^-8) on a few elements, which the
#: layers compound to well under 2% of the largest logit.
PALLAS_LOGIT_RTOL = 0.02
#: explicit TP against one device on the same step: the shards' partial
#: sums are added in the compute dtype, which reorders bf16 additions.
TP_LOSS_ATOL = 0.02
TP_GNORM_RTOL = 0.05

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_EXPECTED_PATH = {"pallas": ops.FUSED, "interpret": ops.INTERPRET}


class SmokeCheckError(RuntimeError):
    """A smoke check failed."""


def _check(ok, what, **detail):
    if not ok:
        raise SmokeCheckError(f"{what}: {json.dumps(detail, default=str)}")


def _emit(record):
    print(json.dumps(record, default=float), flush=True)


@contextlib.contextmanager
def _compile_clock():
    """Sum of XLA compile seconds (persistent-cache reads included) spent
    inside the context, as JAX's own monitoring events report them."""
    total = {"s": 0.0}

    def listen(event, secs, **_):
        if event == _COMPILE_EVENT:
            total["s"] += secs
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield total
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Sizes of the smoke run.  The defaults are the chip run:
    ``gpt2-117m`` at full width, batch 8 x 1024 training, and a 1024-page
    paged engine with 8 slots and a 256-token prefill chunk."""
    arch: str = "gpt2-117m"
    shrink: bool = False          # run cfg.reduced() widths (CPU tests)
    seed: int = 0
    kernels: str = "pallas"       # serving kernel path; CPU tests interpret
    connections: tuple = ("fal", "preln")
    # train
    steps: int = 16
    batch: int = 8
    seq: int = 1024
    lr: float = 1e-3
    # serve: two waves of (shares the prefix?, prompt length) requests;
    # the second wave's shared prompts hit the prefix cache that the first
    # wave parked, and the prompt that IS the prefix forces copy-on-write
    page_size: int = 16
    num_pages: int = 1024
    slots: int = 8
    prefill_chunk: int = 256
    max_seq: int = 1024
    prefix: int = 128
    wave1: tuple = ((True, 192), (False, 64), (False, 320), (False, 512))
    wave2: tuple = ((True, 128), (True, 328), (True, 512), (False, 96))
    max_new: int = 32
    int8_max_new: int = 8

    @classmethod
    def reduced(cls, **kw) -> "SmokeConfig":
        """The same phases at ``ModelConfig.reduced()`` widths, with the
        Pallas kernels interpreted (CPU tests)."""
        base = dict(shrink=True, kernels="interpret", steps=6, batch=4,
                    seq=64, page_size=8, num_pages=128, slots=4,
                    prefill_chunk=16, max_seq=128, prefix=16,
                    wave1=((True, 24), (False, 8), (False, 40), (False, 64)),
                    wave2=((True, 16), (True, 41), (True, 64), (False, 12)),
                    max_new=4, int8_max_new=2)
        base.update(kw)
        return cls(**base)

    def model(self, connection):
        cfg = get_config(self.arch)
        cfg = cfg.reduced() if self.shrink else cfg
        return cfg.replace(connection=connection)

    def batch_at(self, cfg, step=0):
        return SyntheticMarkov(cfg.vocab, self.seq, self.batch,
                               seed=self.seed).batch_at(step)


# --------------------------------------------------------------------------- #
# train: the single-device FAL train step
# --------------------------------------------------------------------------- #
def _reference_loss(cfg, sc):
    """Float32 forward loss of the trainer's step-0 params and batch."""
    params = M.init_params(jax.random.PRNGKey(sc.seed), cfg)
    batch = {"tokens": jnp.asarray(sc.batch_at(cfg)["tokens"])}
    ref_cfg = cfg.replace(dtype="float32")
    with jax.default_matmul_precision("highest"):
        loss, _ = jax.jit(lambda p, b: M.loss_fn(p, ref_cfg, b))(params,
                                                                  batch)
        return float(loss)


def train_phase(sc: SmokeConfig) -> dict:
    """``trainer.train`` for each connection: every loss finite, the step-0
    loss within ``LOSS_ATOL`` of the float32 reference, the last loss below
    the first (step 0 runs at learning rate 0 under ``warmup_cosine``)."""
    out = {"phase": "train", "arch": sc.arch, "batch": sc.batch,
           "seq": sc.seq, "steps": sc.steps}
    for conn in sc.connections:
        cfg = sc.model(conn)
        t0 = time.perf_counter()
        with _compile_clock() as clock:
            _, hist = trainer.train(
                cfg, steps=sc.steps, batch=sc.batch, seq_len=sc.seq,
                lr=sc.lr, seed=sc.seed, plan=ExecutionPlan.single_device(),
                log_every=1)
            ref = _reference_loss(cfg, sc)
        losses = [h["loss"] for h in hist]
        rec = {"dtype": cfg.dtype, "loss_first": losses[0],
               "loss_last": losses[-1], "loss_ref_f32": ref,
               "loss_err": abs(losses[0] - ref), "loss_atol": LOSS_ATOL,
               "compile_s": clock["s"],
               "wall_s": time.perf_counter() - t0}
        _check(len(losses) == sc.steps and bool(np.all(np.isfinite(losses))),
               f"train {conn}: non-finite loss", losses=losses)
        _check(rec["loss_err"] <= LOSS_ATOL,
               f"train {conn}: step-0 loss off the float32 reference", **rec)
        _check(losses[-1] < losses[0], f"train {conn}: loss did not fall",
               **rec)
        out[conn] = rec
    _emit(out)
    return out


# --------------------------------------------------------------------------- #
# serve: the packed paged engine
# --------------------------------------------------------------------------- #
def _prompts(sc, vocab):
    rng = np.random.default_rng(sc.seed)
    prefix = rng.integers(1, vocab, sc.prefix)
    waves = []
    for wave in (sc.wave1, sc.wave2):
        ps = []
        for shared, n in wave:
            if shared:
                p = np.concatenate([prefix, rng.integers(1, vocab,
                                                         n - sc.prefix)])
            else:
                p = rng.integers(1, vocab, n)
            ps.append(p.astype(np.int64))
        waves.append(ps)
    return waves


def _engine_cfg(sc, kv_dtype, prefix_cache):
    return EngineConfig(page_size=sc.page_size, num_pages=sc.num_pages,
                        slots=sc.slots, prefill_chunk=sc.prefill_chunk,
                        max_seq=sc.max_seq, kv_dtype=kv_dtype,
                        prefix_cache=prefix_cache)


def _serve(cfg, params, ecfg, plan, waves, max_new):
    eng = PagedEngine(cfg, params, ecfg, plan=plan)
    rid = 0
    for wave in waves:                   # a wave runs dry before the next
        for p in wave:
            eng.submit(ServeRequest(rid=rid, prompt=p, max_new=max_new))
            rid += 1
        eng.run()
    done = sorted(eng.finished, key=lambda r: r.rid)
    _check(len(done) == rid and all(
        len(r.generated) == max_new and not r.truncated for r in done),
        "serve: a request did not finish",
        finished=[(r.rid, len(r.generated), r.truncated) for r in done])
    return eng


def _probe_logits(cfg, params, sc, plan, kv_dtype, prompts, n_ticks=2):
    """Logits of the first ``n_ticks`` packed ticks over ``prompts``, fed
    identically whatever the plan: ragged prefill segments on tick 1, then
    the rest of the prompts plus one-token decode segments for lanes whose
    prompt is done.  Returns (live segments, vocab) float32."""
    S = sc.slots
    nb = pages_needed(sc.max_seq, sc.page_size)
    cache = M.init_paged_cache(cfg, sc.num_pages, sc.page_size, S,
                               kv_dtype=kv_dtype)
    bt = jnp.asarray(1 + np.arange(S * nb, dtype=np.int32).reshape(S, nb)
                     % (sc.num_pages - 1))
    step = make_packed_step(cfg, plan)
    known = [list(p) for p in prompts] + [[] for _ in range(S - len(prompts))]
    pos = [0] * S
    budget = S + sc.prefill_chunk - 1
    z_f, z_i = jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32)
    out = []
    for tick in range(n_ticks):
        lists = [k[p:p + sc.prefill_chunk] for k, p in zip(known, pos)]
        dec = [len(k) - p == 1 for k, p in zip(known, pos)]
        pt = pack_tokens(lists, pos, dec, budget, rotate=tick)
        sample_pos = jnp.asarray(np.asarray(pos) + pt.n_taken, jnp.int32)
        logits, _, cache = step(
            params, cache, jnp.asarray(pt.tokens), jnp.asarray(pt.tok_slot),
            jnp.asarray(pt.tok_pos), bt, jnp.asarray(pt.seg_last), z_f, z_i,
            jnp.ones((S,), jnp.float32), z_i, sample_pos)
        out.append(np.asarray(logits, np.float32)[pt.seg_last >= 0])
        for i in range(S):
            pos[i] += int(pt.n_taken[i])
            if known[i] and pos[i] == len(known[i]):
                known[i].append(known[i][0])     # decode segment next tick
    return np.concatenate(out)


def serve_phase(sc: SmokeConfig) -> dict:
    """``PagedEngine`` on ``connection=fal`` with bf16 pages and the prefix
    cache: every request finishes, the second wave hits the cache and
    copy-on-write runs, and the packed-attention and page-copy kernels ran
    on the plan's kernel path.  Then one tick on the Pallas path is checked
    against the same tick on the gather oracle, and an int8-page engine
    serves the same requests within the int8 logit bound."""
    t0 = time.perf_counter()
    with _compile_clock() as clock:
        out = _serve_checks(sc)
    out.update(compile_s=clock["s"], wall_s=time.perf_counter() - t0)
    _emit(out)
    return out


def _serve_checks(sc):
    cfg = sc.model("fal")
    params = M.init_params(jax.random.PRNGKey(sc.seed), cfg)
    waves = _prompts(sc, cfg.vocab)
    plan = ExecutionPlan.single_device().with_kernels(sc.kernels)
    want = _EXPECTED_PATH[sc.kernels]
    out = {"phase": "serve", "arch": sc.arch, "connection": "fal",
           "kernels": sc.kernels}

    # dispatch labels are recorded when a program is traced, so each engine
    # runs before anything else traces its kernels at the same shapes
    ops.reset_dispatch_paths()
    eng = _serve(cfg, params, _engine_cfg(sc, "bf16", True), plan, waves,
                 sc.max_new)
    st = eng.stats()
    paths = ops.dispatch_paths()
    n_shared2 = sum(s for s, _ in sc.wave2)
    out["bf16"] = {"requests": len(eng.finished), "ticks": st["ticks"],
                   "prefix_hits": st["prefix"]["hits"],
                   "cow_copies": st["prefix"]["cow_copies"],
                   "dispatches_per_tick": st["dispatches_per_tick"],
                   "dispatch_paths": paths}
    _check(st["prefix"]["hits"] >= n_shared2 and st["prefix"]["cow_copies"],
           "serve: prefix cache / copy-on-write did not run", **out["bf16"])
    _check(paths.get("paged_packed_attention") == want
           and paths.get("copy_pages") == want,
           f"serve: kernels not on the {want} path", paths=paths)

    ops.reset_dispatch_paths()
    eng8 = _serve(cfg, params, _engine_cfg(sc, "int8", False), plan, waves,
                  sc.int8_max_new)
    paths8 = ops.dispatch_paths()
    out["int8"] = {"requests": len(eng8.finished), "dispatch_paths": paths8}
    _check(paths8.get("paged_packed_attention.int8") == want,
           f"serve: int8 kernel not on the {want} path", paths=paths8)

    prompts = waves[0]
    lg = _probe_logits(cfg, params, sc, plan, "bf16", prompts)
    lg_ref = _probe_logits(cfg, params, sc, plan.with_kernels("ref"), "bf16",
                           prompts)
    lg8 = _probe_logits(cfg, params, sc, plan, "int8", prompts)
    scale = float(np.max(np.abs(lg_ref)))
    out["tick_vs_oracle"] = {
        "segments": int(lg.shape[0]), "max_abs_err":
            float(np.max(np.abs(lg - lg_ref))), "max_abs_logit": scale,
        "rtol": PALLAS_LOGIT_RTOL}
    out["int8_vs_bf16"] = {
        "max_abs_err": float(np.max(np.abs(lg8 - lg))),
        "max_abs_logit": float(np.max(np.abs(lg))), "rtol": INT8_LOGIT_RTOL}
    _check(bool(np.all(np.isfinite(lg))) and bool(np.all(np.isfinite(lg8))),
           "serve: non-finite probe logits")
    _check(out["tick_vs_oracle"]["max_abs_err"] <= PALLAS_LOGIT_RTOL * scale,
           "serve: Pallas tick off the gather oracle",
           **out["tick_vs_oracle"])
    _check(out["int8_vs_bf16"]["max_abs_err"]
           < INT8_LOGIT_RTOL * out["int8_vs_bf16"]["max_abs_logit"],
           "serve: int8 tick outside its logit bound", **out["int8_vs_bf16"])
    return out


# --------------------------------------------------------------------------- #
# tp: the explicit-TP train step on a data=1 x model=N mesh
# --------------------------------------------------------------------------- #
def _sharded_leaves(tree, n):
    """(sharded, total) param leaves: sharded ones span all ``n`` devices
    and hold a strict slice of the leaf on each."""
    leaves = jax.tree.leaves(tree)
    sharded = [x for x in leaves
               if len(x.sharding.device_set) == n
               and x.addressable_shards[0].data.shape != x.shape]
    return len(sharded), len(leaves)


def tp_phase(sc: SmokeConfig, devices) -> dict:
    """One explicit-TP train step per connection against the same step on
    one device with the same params and batch: loss within
    ``TP_LOSS_ATOL``, grad norm within ``TP_GNORM_RTOL``.  Prints each
    compiled program's all-reduce count and checks that the sharded
    parameters really are spread over the mesh."""
    n = len(devices)
    mesh = MX.make_mesh((1, n), ("data", "model"), devices=devices)
    out = {"phase": "tp", "arch": sc.arch, "mesh": {"data": 1, "model": n},
           "batch": sc.batch, "seq": sc.seq}
    for conn in sc.connections:
        cfg = sc.model(conn)
        ocfg = adamw.AdamWConfig(lr=sc.lr)
        plan = ExecutionPlan.from_mesh(mesh, tp="explicit").validate(cfg)
        state = tstep.init_state(jax.random.PRNGKey(sc.seed), cfg, ocfg)
        batch = {"tokens": sc.batch_at(cfg)["tokens"]}
        st_sh = MX.shardings_for(mesh, MX.state_specs(state, cfg))
        b_sh = {"tokens": NamedSharding(mesh, MX.batch_spec(mesh))}
        state_tp = jax.device_put(state, st_sh)
        batch_tp = jax.device_put(batch, b_sh)
        with _compile_clock() as clock:
            step_tp = jax.jit(tstep.make_train_step(cfg, ocfg, plan),
                              in_shardings=(st_sh, b_sh),
                              out_shardings=(st_sh, None)
                              ).lower(state_tp, batch_tp).compile()
            state_1 = jax.device_put(state, devices[0])
            batch_1 = jax.device_put(batch, devices[0])
            step_1 = jax.jit(tstep.make_train_step(
                cfg, ocfg, ExecutionPlan.single_device())
            ).lower(state_1, batch_1).compile()
        new_tp, m_tp = step_tp(state_tp, batch_tp)
        _, m_1 = step_1(state_1, batch_1)
        n_sharded, n_leaves = _sharded_leaves(new_tp["params"], n)
        rec = {
            "loss_tp": float(m_tp["loss"]), "loss_1": float(m_1["loss"]),
            "grad_norm_tp": float(m_tp["grad_norm"]),
            "grad_norm_1": float(m_1["grad_norm"]),
            "all_reduce": {
                "tp": TP.count_collectives(step_tp.as_text()).get(
                    "all-reduce", 0),
                "one_device": TP.count_collectives(step_1.as_text()).get(
                    "all-reduce", 0)},
            "sharded_param_leaves": n_sharded, "param_leaves": n_leaves,
            "bytes_in_use": [d.memory_stats().get("bytes_in_use")
                             if d.memory_stats() else None
                             for d in devices],
            "compile_s": clock["s"]}
        _check(abs(rec["loss_tp"] - rec["loss_1"]) <= TP_LOSS_ATOL,
               f"tp {conn}: loss off the one-device step", **rec)
        _check(abs(rec["grad_norm_tp"] - rec["grad_norm_1"])
               <= TP_GNORM_RTOL * rec["grad_norm_1"],
               f"tp {conn}: grad norm off the one-device step", **rec)
        _check(n_sharded > 0, f"tp {conn}: no parameter is sharded", **rec)
        out[conn] = rec
    _emit(out)
    return out


# --------------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the explicit-TP train step on a "
                         "data=1 x model=4 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    print(f"chip_smoke: {len(devices)} x {dev.device_kind}, compile cache "
          f"{cache_dir}", flush=True)
    sc = SmokeConfig(seed=args.seed)
    if args.chips == 4:
        tp_phase(sc, devices[:4])
        count = 4
    else:
        train_phase(sc)
        serve_phase(sc)
        count = len(devices)
    _emit({"ok": True, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind, "count": count}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
