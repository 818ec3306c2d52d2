"""Paper Fig 2 / Fig 6 / Fig 7 (structural): per-block TP collective counts
and bytes, measured on the REAL ``DecoderLM`` block stack lowered through
``models/model.py::decoder_stack_tp`` (the production shard_map partial-sum
path — the toy duplicate-weight stack is gone).  ``hlo_cost.analyze`` is
while-loop aware, so the scanned layers count once per layer and the
fal/preln all-reduce-bytes ratio must land on the paper's (L+1)/(2L):
fal pays one collective per steady-state block plus block 0's extra
first-attention assemble, preln pays two per block.

With ``sp=True`` the same modes are additionally lowered under the
sequence-parallel ``ExecutionPlan`` (Megatron-SP LN regions) and the bench
asserts the layout's contract on the HLO:

  * reduce-op count is preserved — every replicated all-reduce becomes
    exactly one reduce-scatter (block 0's first-attention export stays the
    one true all-reduce);
  * reduce bytes shrink by exactly tp_size —
    ar_bytes_sp + tp * rs_bytes_sp == ar_bytes_replicated.

Run in a subprocess-free way by forcing host devices BEFORE jax import (the
harness in run.py does this)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks import hlo_cost
from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.optim import grad_compress

N_LAYERS = 8
TP = 8


def _collect(txt):
    r = hlo_cost.analyze(txt)["collectives"]
    zero = {"bytes": 0, "count": 0}
    return {op: r.get(op, zero) for op in
            ("all-reduce", "reduce-scatter", "all-gather")}


def bench(csv, sp=False):
    assert len(jax.devices()) >= TP, "run via benchmarks.run (forces devices)"
    mesh = make_mesh((TP,), ("model",))
    cfg0 = get_config("llama3.2-3b").reduced().replace(
        n_layers=N_LAYERS, n_heads=8, n_kv_heads=8)
    B, S = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg0.d_model))
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    rows, sp_rows = {}, {}
    for mode in ("preln", "parallel", "fal", "falplus"):
        cfg = cfg0.replace(connection=mode)
        params = M.init_params(jax.random.PRNGKey(0), cfg)

        def lower(plan, cfg=cfg, params=params):
            def fwd(p, x):
                return M.decoder_stack_tp(p, cfg, x, positions, plan)[0]
            t0 = time.time()
            txt = jax.jit(fwd).lower(params, x).compile().as_text()
            return _collect(txt), time.time() - t0

        plan = ExecutionPlan.from_mesh(mesh, tp="explicit").validate(cfg)
        c, lower_s = lower(plan)
        ar = c["all-reduce"]
        rows[mode] = {"count": ar["count"], "bytes": ar["bytes"]}
        csv(f"comm_fig2_{mode}", lower_s * 1e6,
            f"allreduce_count={ar['count']:.0f};bytes={ar['bytes']:.0f}")

        if sp:
            plan_sp = ExecutionPlan.from_mesh(mesh, tp="explicit",
                                              sp=True).validate(cfg)
            c_sp, lower_s = lower(plan_sp)
            ar_sp, rs, ag = (c_sp["all-reduce"], c_sp["reduce-scatter"],
                             c_sp["all-gather"])
            sp_rows[mode] = {
                "allreduce": dict(count=ar_sp["count"], bytes=ar_sp["bytes"]),
                "reduce_scatter": dict(count=rs["count"], bytes=rs["bytes"]),
                "all_gather": dict(count=ag["count"], bytes=ag["bytes"]),
            }
            csv(f"comm_sp_{mode}", lower_s * 1e6,
                f"rs_bytes={rs['bytes']:.0f};ag_bytes={ag['bytes']:.0f};"
                f"ar_bytes={ar_sp['bytes']:.0f}")
            # the SP contract: reduce-op count preserved, reduce bytes / tp
            assert ar_sp["count"] + rs["count"] == ar["count"], \
                (mode, c_sp, ar)
            assert ar_sp["bytes"] + TP * rs["bytes"] == ar["bytes"], (
                f"{mode}: SP reduce bytes not cut by tp={TP}: "
                f"ar_sp={ar_sp['bytes']} + {TP}*rs={rs['bytes']} != "
                f"ar_replicated={ar['bytes']}")
            csv(f"comm_sp_{mode}_bytes_reduction", 0,
                f"{ar['bytes'] / max(rs['bytes'] + ar_sp['bytes'], 1):.3f}")

    # the paper's claim: fal ~ half of preln (steady state; block0 pays one
    # extra assemble -> (L+1)/(2L))
    ratio = rows["fal"]["bytes"] / max(rows["preln"]["bytes"], 1)
    expected = (N_LAYERS + 1) / (2 * N_LAYERS)
    csv("comm_fig2_ratio_fal_over_preln", 0, f"{ratio:.3f}")
    csv("comm_fig2_ratio_expected", 0, f"{expected:.3f}")
    assert abs(ratio - expected) < 0.02, (
        f"DecoderLM fal/preln all-reduce bytes {ratio:.3f} != "
        f"(L+1)/(2L) = {expected:.3f}")

    # Fig 7: gradient-compression payloads (lossy baselines)
    payloads = {}
    g = {"w%d" % i: jax.random.normal(jax.random.PRNGKey(i), (256, 256))
         for i in range(4)}
    for method in ("none", "int8", "lowrank"):
        b = grad_compress.compressed_bytes(g, method)
        payloads[method] = b
        csv(f"comm_fig7_payload_{method}", 0, str(b))

    # Measured TP gradient wire bytes: lower value_and_grad of the fal stack
    # per ExecutionPlan.grad_compress method and read per-device ring-model
    # payload off the compiled HLO (core/tp.py::collective_payload_bytes —
    # NOT output-shape bytes, which would misrank the int8 all_to_all/
    # all_gather exchange).  Gradient payload = payload(grad HLO) −
    # payload(fwd HLO): the backward cotangent reductions only.  The small
    # exact residue under compression is the LN parameter-gradient psums
    # shard_map's transpose inserts for replicated params.
    from repro.core.tp import collective_payload_bytes
    cfg_g = cfg0.replace(connection="fal")
    params_g = M.init_params(jax.random.PRNGKey(0), cfg_g)
    B_G, S_G = 4, 64            # training-shaped batch: activation cotangents
    x_g = jax.random.normal(jax.random.PRNGKey(2), (B_G, S_G, cfg0.d_model))
    pos_g = jnp.broadcast_to(jnp.arange(S_G)[None], (B_G, S_G))
    grad_payloads, fwd_payload = {}, 0
    for method in grad_compress.GRAD_COMPRESS_METHODS:
        plan_g = ExecutionPlan.from_mesh(
            mesh, tp="explicit", grad_compress=method).validate(cfg_g)

        def loss(p, xx, plan=plan_g):
            y = M.decoder_stack_tp(p, cfg_g, xx, pos_g, plan)[0]
            return jnp.mean(y * y)

        t0 = time.time()
        hlo_f = jax.jit(loss).lower(params_g, x_g).compile().as_text()
        hlo_g = jax.jit(jax.value_and_grad(loss)).lower(
            params_g, x_g).compile().as_text()
        lower_s = time.time() - t0
        pf = sum(collective_payload_bytes(hlo_f, TP).values())
        pg = sum(collective_payload_bytes(hlo_g, TP).values())
        grad_payloads[method] = pg - pf
        if method == "none":
            fwd_payload = pf
        csv(f"comm_grad_payload_{method}", lower_s * 1e6,
            f"grad_bytes={pg - pf};fwd_bytes={pf}")
    assert grad_payloads["int8"] <= 0.3 * grad_payloads["none"], (
        f"grad_compress=int8 gradient payload not <=0.3x of none: "
        f"{grad_payloads}")
    assert grad_payloads["lowrank"] < grad_payloads["none"], grad_payloads
    csv("comm_grad_payload_ratio_int8_over_none", 0,
        f"{grad_payloads['int8'] / max(grad_payloads['none'], 1):.3f}")

    return {"model": cfg0.arch_id, "n_layers": N_LAYERS, "tp_size": TP,
            "batch": B, "seq": S, "d_model": cfg0.d_model,
            "allreduce_per_mode": rows,
            "sp": sp_rows,
            "ratio_fal_over_preln": ratio, "ratio_expected": expected,
            "fig7_payload_bytes": payloads,
            "grad_payload_bytes": grad_payloads,
            "grad_payload_fwd_bytes": fwd_payload}
