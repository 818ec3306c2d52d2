"""Train step: loss -> grad (with microbatched gradient accumulation) ->
AdamW update.  Built once per (cfg, ExecutionPlan) and jitted by the caller
(launch/train.py, launch/dryrun.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.plan import ExecutionPlan, Phase
from repro.models import model as M
from repro.obs.trace import layer_scope
from repro.optim import adamw


def make_loss_fn(cfg, plan=None):
    plan = ExecutionPlan.resolve(plan)

    def loss(params, batch):
        l, metrics = M.loss_fn(params, cfg, batch, plan)
        return l, metrics
    return loss


def make_train_step(cfg, ocfg: adamw.AdamWConfig, plan=None,
                    num_microbatches: int = 1, grad_shardings=None):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {"params", "opt"}.  ``batch["tokens"]``: (B, S); B is split into
    ``num_microbatches`` sequential microbatches (lax.scan) with gradient
    accumulation — bounds activation (and MoE dispatch-buffer) memory.
    ``plan`` (ExecutionPlan) flows
    unchanged into the model: with ``tp='explicit'`` the decoder family's
    loss/grad run through the shard_map partial-sum TP stack
    (model.decoder_stack_tp) — the paper's per-block collective structure —
    instead of implicit GSPMD sharding, and with ``sp=True`` the
    inter-block activations additionally stay sequence-sharded over the
    model axis (reduce-scatter/all-gather LN regions); the collectives
    differentiate, so the same step covers every layout.
    ``grad_shardings``: NamedSharding tree matching params — pins the
    accumulated-gradient buffer to the param layout (otherwise GSPMD may
    replicate it, which at 671B scale is fatal).
    """
    plan = ExecutionPlan.resolve(plan).with_phase(Phase.TRAIN)
    plan.validate(cfg)
    loss_fn = make_loss_fn(cfg, plan)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def pin(g):
        if grad_shardings is None:
            return g
        return jax.lax.with_sharding_constraint(g, grad_shardings)

    def train_step(state, batch):
        params = state["params"]

        if num_microbatches == 1:
            (l, metrics), grads = grad_fn(params, batch)
            grads = pin(grads)
        else:
            def split(x):
                return x.reshape((num_microbatches, -1) + x.shape[1:])
            micro = jax.tree.map(split, batch)

            def acc(carry, mb):
                g_acc, l_acc = carry
                (l, _), g = grad_fn(params, mb)
                g_new = pin(jax.tree.map(jnp.add, g_acc, pin(g)))
                return (g_new, l_acc + l), None

            g0 = pin(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  params))
            (g_sum, l_sum), _ = jax.lax.scan(acc, (g0, 0.0), micro)
            grads = jax.tree.map(lambda g: g / num_microbatches, g_sum)
            l = l_sum / num_microbatches
            metrics = {}

        with layer_scope("optimizer"):
            new_params, new_opt, gnorm = adamw.adamw_update(
                params, grads, state["opt"], ocfg)
        metrics = dict(metrics, loss=l, grad_norm=gnorm)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def init_state(key, cfg, ocfg: adamw.AdamWConfig):
    params = M.init_params(key, cfg)
    return {"params": params, "opt": adamw.init_opt_state(params, ocfg)}


def make_eval_step(cfg, plan=None):
    plan = ExecutionPlan.resolve(plan).with_phase(Phase.EVAL)
    plan.validate(cfg)
    loss_fn = make_loss_fn(cfg, plan)

    def eval_step(params, batch):
        l, metrics = loss_fn(params, batch)
        return dict(metrics, loss=l, ppl=jnp.exp(metrics["ce"]))
    return eval_step
