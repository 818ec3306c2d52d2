"""Quickstart: build a FAL model, run a forward pass, train a few steps, and
show the TP all-reduce halving — the paper's contribution in ~60 lines.

Execution layout is selected with a typed ``ExecutionPlan`` (core/plan.py):
single device, GSPMD mesh, explicit partial-sum TP, or explicit TP with
sequence-parallel LN regions.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core import tp
from repro.core.plan import ExecutionPlan
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.train import trainer

# ---- 1. a reduced llama3.2 with the paper's FAL connection ----------------
cfg = get_config("llama3.2-3b").reduced().replace(connection="fal")
params = M.init_params(jax.random.PRNGKey(0), cfg)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                      cfg.vocab)}
plan = ExecutionPlan.single_device()          # phase=train, no mesh, no TP
logits, aux, _ = M.forward(params, cfg, batch, plan)
print(f"forward: logits {logits.shape}, FAL connection = {cfg.connection}")

# ---- 2. train a few steps on the synthetic Markov corpus ------------------
state, hist = trainer.train(cfg, steps=30, batch=8, seq_len=64, plan=plan,
                            log_every=10)

# ---- 3. the paper's point: FAL halves per-block TP all-reduces -------------
# make_tp_forward builds REAL DecoderLM blocks and runs them through the
# explicit partial-sum shard_map stack (model.decoder_stack_tp) under an
# ExecutionPlan.from_mesh(mesh, tp="explicit") — the HLO below is the
# production collective structure, not a toy's
mesh = make_mesh((8,), ("model",))
for mode in ("preln", "fal"):
    init, fwd = tp.make_tp_forward(mesh, n_layers=4, d=64, d_ff=256,
                                   n_heads=8, mode=mode)
    p = init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    hlo = fwd.lower(p, x).compile().as_text()
    counts = tp.count_collectives(hlo)
    print(f"{mode:7s}: HLO all-reduces = {counts.get('all-reduce', 0)} "
          f"(scan body counted once; steady-state per block: "
          f"{2 if mode == 'preln' else 1})")

# ---- 4. sequence-parallel LN regions (ExecutionPlan sp=True) ---------------
# same reduce-collective count, but the inter-block activations stay
# sharded over the model axis: each all-reduce becomes a reduce-scatter at
# 1/tp the bytes (block 0 keeps the one all-reduce that exports the
# first-attention signal)
sp_plan = ExecutionPlan.from_mesh(mesh, tp="explicit", sp=True)
# validate raises loud errors for bad head/tp divisibility etc. — the
# 8-head bench stack divides the 8-way model axis; the 4-head reduced
# llama above would be rejected here, not deep inside a shard_map trace
sp_plan.validate(tp.bench_stack_config(4, 64, 256, 8, "fal"))
for mode in ("preln", "fal"):
    init, fwd = tp.make_tp_forward(mesh, n_layers=4, d=64, d_ff=256,
                                   n_heads=8, mode=mode, sp=True)
    p = init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    b = tp.collective_bytes(fwd.lower(p, x).compile().as_text())
    print(f"{mode:7s} sp: reduce-scatter bytes = {b.get('reduce-scatter', 0)}"
          f" (vs all-reduce bytes {b.get('all-reduce', 0)} kept by block 0),"
          f" all-gather bytes = {b.get('all-gather', 0)}")
