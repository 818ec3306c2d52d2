"""Tensor-parallel tooling for the unified decoder family.

The explicit partial-sum TP execution itself lives with the model now:
``models/blocks.py::block_apply`` composes head-/hidden-/expert-sharded
local kernels per ``core/fal.py::attention_must_assemble`` and
``models/model.py::decoder_stack_tp`` drives the whole block stack under one
shard_map, selected by an explicit-TP ``core.plan.ExecutionPlan`` (the toy
duplicate-weight stack that used to live here is gone).  Per transformer
block and connection mode the collective structure is the paper's Fig 2:

  preln / falplus : all-reduce(MHA partial) -> MLP -> all-reduce(MLP) = 2
  fal / parallel  : MHA partial + MLP partial added LOCALLY -> ONE all-reduce
  block 0 (fal)   : one extra assemble to export the first-attention signal
                    -> (L+1)/(2L) all-reduce bytes vs preln over L layers

With ``ExecutionPlan(sequence_parallel=True)`` the same structure lowers in
the Megatron-SP layout: every all-reduce above becomes a reduce-scatter at
1/tp the bytes behind an all-gather of the LN region (block 0's signal
export stays the one true all-reduce).

This module keeps what is reusable across tests and benchmarks:

  * ``make_tp_forward`` — thin wrapper that builds a real-``DecoderLM``
    block stack (``models/blocks.py`` weights, GQA attention, cfg.mlp FFN)
    and returns (init_fn, jitted forward) running ``decoder_stack_tp`` on a
    given mesh — the structural harness for asserting the halving (and the
    SP bytes reduction, ``sp=True``) on lowered HLO without hardware.
  * ``count_collectives`` / ``collective_bytes`` — HLO-text parsers for
    collective op counts and payload bytes (scan bodies counted once; use
    ``benchmarks.hlo_cost.analyze`` for trip-count-aware totals).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp


def bench_stack_config(n_layers, d, d_ff, n_heads, mode):
    """A minimal real-model dense config for TP structure tests/benches."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        arch_id="tp-bench", family="dense", n_layers=n_layers, d_model=d,
        n_heads=n_heads, n_kv_heads=n_heads, d_ff=d_ff, vocab=256,
        connection=mode, norm="layernorm", mlp="gelu", dtype="float32",
        param_dtype="float32", remat=False, attn_block_q=64)


def make_tp_forward(mesh, n_layers, d, d_ff, n_heads, mode, axis="model",
                    sp=False, grad_compress="none"):
    """(init_fn, jitted forward) for an n_layer unified-block TP stack.

    The params are real ``models/blocks.py`` block weights (the same trees
    ``DecoderLM`` trains); the forward is ``models/model.py::
    decoder_stack_tp`` on ``mesh`` under an explicit-TP ``ExecutionPlan``
    — so HLO lowered from here IS the production collective structure, not
    a toy's.  ``sp=True`` lowers the sequence-parallel layout (activations
    sharded over ``axis`` along the sequence; reduce-scatter/all-gather
    pairs instead of all-reduces).  ``grad_compress`` ∈ {none, int8,
    lowrank} routes the BACKWARD cotangent reductions through
    ``optim/grad_compress.py``'s compressed collectives (forward HLO is
    unchanged; ``bench_comm`` diffs the gradient wire bytes).
    """
    from repro.core.plan import ExecutionPlan
    from repro.models import blocks as BL
    from repro.models import model as M

    cfg = bench_stack_config(n_layers, d, d_ff, n_heads, mode)
    plan = ExecutionPlan.from_mesh(mesh, tp="explicit", sp=sp,
                                   model_axis=axis,
                                   grad_compress=grad_compress).validate(cfg)

    def init_fn(key):
        k0, ks = jax.random.split(key)
        p = {"block0": BL.block_init(k0, cfg, is_block0=True)}
        if n_layers > 1:
            p["blocks_dense"] = jax.vmap(
                lambda k: BL.block_init(k, cfg))(
                jax.random.split(ks, n_layers - 1))
        return p

    def fwd(params, x):
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        y, _ = M.decoder_stack_tp(params, cfg, x, positions, plan)
        return y

    return init_fn, jax.jit(fwd)


def make_tp_decode_step(mesh, d, d_ff, n_heads, mode, axis="model", *,
                        dual=False, page_size=8, num_pages=9):
    """(init_fn, jitted paged decode tick) for ONE steady-state block under
    an explicit-TP shard_map — the structural harness for the dual-branch
    collectives gate: lowering the same tick with ``dual=False`` and
    ``dual=True`` and diffing ``count_collectives`` asserts that MHA||MLP
    branch parallelism adds NO collectives (both pay the single fused
    all-reduce of the fal/parallel steady state; kept by
    ``models/blocks.py::_block_apply_dual`` merging the MHA and MLP partial
    sums before the one psum).

    ``init_fn(key)`` returns (block_params, paged kv cache); the step is
    ``step(params, x (B,1,d), cache, block_tables (B,T), pos (B,),
    n_valid (B,), a1_sig (B,1,d)) -> (x_out, new_cache)``.
    """
    from jax.sharding import PartitionSpec as P
    from repro.core.plan import ExecutionPlan, Phase
    from repro.launch import mesh as MX
    from repro.models import attention as A
    from repro.models import blocks as BL

    cfg = bench_stack_config(1, d, d_ff, n_heads, mode)
    plan = ExecutionPlan.from_mesh(mesh, tp="explicit", phase=Phase.PAGED,
                                   model_axis=axis,
                                   dual_branch=dual).validate(cfg)
    inner = plan.inner()

    def init_fn(key):
        params = BL.block_init(key, cfg, kind="dense")
        cache = A.gqa_init_paged_cache(cfg, num_pages, page_size,
                                       cfg.dtype)
        return params, cache

    kv = P(None, None, axis, None)               # pages: Hkv over model

    def step(params, x, cache, bt, pos, n_valid, a1_sig):
        wspecs = MX.param_specs(params, cfg)

        def local(bp, x, ck, cv, bt, pos, n_valid, sig):
            out, _, _, new_cache = BL.block_apply(
                bp, cfg, x, sig, None, 0, kind="dense", is_block0=False,
                plan=inner, cache={"k": ck, "v": cv}, pos=pos,
                block_tables=bt, n_valid=n_valid)
            return out, new_cache["k"], new_cache["v"]

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(wspecs, P(), kv, kv, P(), P(), P(), P()),
                           out_specs=(P(), kv, kv),
                           check_vma=False)
        out, ck, cv = fn(params, x, cache["k"], cache["v"], bt, pos,
                         n_valid, a1_sig)
        return out, {"k": ck, "v": cv}

    return init_fn, jax.jit(step)


def assert_dual_no_extra_collectives(mesh, modes=("fal", "parallel"), *,
                                     check_numeric=True):
    """THE dual-branch structural gate, shared by
    ``benchmarks/bench_serving.py --dual`` and ``tests/test_dual_branch.py``
    (one implementation so the two cannot drift): per mode, lower one
    steady-state block's paged decode tick via ``make_tp_decode_step`` with
    and without ``dual`` and assert the collective counts are IDENTICAL —
    both pay exactly ONE fused all-reduce — and (``check_numeric``) that the
    outputs match.  Returns {mode: {"sequential": counts, "dual": counts}}.
    Needs >= 2 devices in ``mesh``.
    """
    import numpy as np
    B, T, page, d = 2, 4, 8, 64
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, d))
    bt = jnp.asarray(np.arange(1, 1 + B * T).reshape(B, T), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    nv = jnp.ones((B,), jnp.int32)
    sig = jax.random.normal(jax.random.PRNGKey(2), (B, 1, d))
    result = {}
    for mode in modes:
        counts, outs = {}, {}
        for dual in (False, True):
            init_fn, step = make_tp_decode_step(mesh, d, 128, 4, mode,
                                                dual=dual, page_size=page)
            params, cache = init_fn(jax.random.PRNGKey(0))
            with mesh:
                hlo = step.lower(params, x, cache, bt, pos, nv,
                                 sig).compile().as_text()
                outs[dual], _ = step(params, x, cache, bt, pos, nv, sig)
            counts["dual" if dual else "sequential"] = \
                count_collectives(hlo)
        assert counts["sequential"].get("all-reduce", 0) == 1, (mode, counts)
        assert counts["dual"] == counts["sequential"], (mode, counts)
        if check_numeric:
            err = float(jnp.max(jnp.abs(outs[True] - outs[False])))
            assert err < 1e-5, (mode, err)
        result[mode] = counts
    return result


# ------------------------------------------------------------------------- #
_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\b")


def count_collectives(hlo_text: str):
    """Count collective ops in HLO text (instruction definitions only)."""
    counts = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        # match op definitions: "%x = bf16[...] all-reduce(..." etc.
        m = re.search(r"=\s+\S+\s+(all-reduce|all-gather|reduce-scatter|"
                      r"all-to-all|collective-permute)(-start)?\(", line)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


_DT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "s32": 4, "u32": 4,
             "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}


def collective_bytes(hlo_text: str):
    """Sum output-shape bytes of collective ops in HLO text (roofline ICI
    term).  Parses shapes like 'bf16[2,16,128]{...}'."""
    total = {}
    pat = re.compile(r"=\s+\(?([a-z0-9]+)\[([0-9,]*)\][^)]*?\s+"
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start)?\(")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if not m:
            continue
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total[op] = total.get(op, 0) + n * _DT_BYTES[dt]
    return total


_COLL_DEF_RE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def collective_payload_bytes(hlo_text: str, tp: int):
    """Per-device WIRE bytes of every collective in HLO text under a ring
    model — the quantity gradient compression actually shrinks (the naive
    output-shape sum misranks e.g. an int8 all_gather whose OUTPUT is full
    size but whose wire traffic is 1/tp of it):

      all-reduce      2·out·(tp-1)/tp   (reduce-scatter + all-gather ring)
      all-gather        out·(tp-1)/tp   (out = the gathered full tensor)
      reduce-scatter    out·(tp-1)      (out = the reduced shard)
      all-to-all        out·(tp-1)/tp   (keeps 1/tp of its own data local)
      collective-permute out

    Unlike ``collective_bytes`` this handles TUPLE-output collectives (XLA
    lowers ``lax.all_to_all`` to one, which the single-shape regex drops)
    by summing every shape token in the output type.  ``-done`` halves of
    async pairs are skipped; ``-start`` counts once.  Returns
    {op: per-device wire bytes}."""
    total = {}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        m = _COLL_DEF_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        out = 0
        for dt, dims in _SHAPE_RE.findall(line[line.index(" = "):m.start()]):
            if dt not in _DT_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            out += n * _DT_BYTES[dt]
        if op == "all-reduce":
            wire = 2 * out * (tp - 1) // tp
        elif op in ("all-gather", "all-to-all"):
            wire = out * (tp - 1) // tp
        elif op == "reduce-scatter":
            wire = out * (tp - 1)
        else:
            wire = out
        total[op] = total.get(op, 0) + wire
    return total
