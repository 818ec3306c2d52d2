"""Meshes + PartitionSpec trees.

``make_mesh`` is the one mesh constructor (Auto axes); ``make_host_mesh``
lays a (data, model) mesh over the devices present (``launch/train.py
--mesh``); the production shapes below exist only for the dry-run, which
compiles them against described devices.

Single pod: (16, 16) over ("data", "model") — 256 chips (v5e pod).
Multi-pod:  (2, 16, 16) over ("pod", "data", "model") — 512 chips.  The
``pod`` axis composes with ``data`` (pure DP across pods): only the gradient
all-reduce crosses pods, never TP collectives.

``param_specs`` mirrors any model's param pytree with Megatron-style specs:
attention heads + FFN hidden over ``model`` (column/row), vocab over
``model``, MoE experts over ``model`` (expert parallelism), Mamba mixers
replicated over ``model`` (sharded over batch only; DESIGN.md §4).  Stacked
(scan) parameter trees get leading ``None``s automatically.

These specs serve double duty: GSPMD layout hints for the implicit path,
and the shard_map ``in_specs`` of the explicit partial-sum TP stack
(``models/model.py::decoder_stack_tp`` — select it with
``core.plan.ExecutionPlan.from_mesh(mesh, tp="explicit")``; add ``sp=True``
for the sequence-parallel LN-region layout, where the activation specs put
the sequence dim on ``model`` instead of replicating it).  The column/row
orientation is what makes the blocks' local kernels return partial sums
there.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P


def make_mesh(shape, axes, *, devices=None):
    """The repo's one mesh constructor: ``jax.make_mesh`` with every axis
    ``Auto``.  The sharded code relies on GSPMD propagation through
    reshapes and gathers, which Explicit-typed axes (the JAX default) turn
    into ``ShardingTypeError``s.  ``devices`` defaults to
    ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_host_mesh(model: int):
    """(data, model) mesh over every device of this host, ``model`` of them
    along the tensor-parallel axis."""
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(f"model axis {model} does not divide the {n} "
                         f"devices present")
    return make_mesh((n // model, model), ("data", "model"))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


MODEL = "model"

# base (unstacked) ndim and spec per leaf key; stacking prepends Nones.
# column = output-dim sharded; row = input-dim sharded (Megatron).
_BASE = {
    # embeddings / heads
    "emb": (2, P(MODEL, None)),
    "pos_emb": (2, P()),
    "enc_pos": (2, P()),
    # norms / scalars / ssm per-head params
    "scale": (1, P()), "bias": (1, P()),
    "a_log": (1, P()), "D": (1, P()), "dt_bias": (1, P()),
    "conv_b": (1, P()), "conv_w": (2, P()),
    # attention (GQA)
    "wq": (2, P(None, MODEL)), "wk": (2, P(None, MODEL)),
    "wv": (2, P(None, MODEL)),
    "wo": (2, P(MODEL, None)),
    # MLA: down-projections replicated (small), up-projections column
    "w_dq": (2, P()), "w_dkv": (2, P()), "w_kr": (2, P()),
    "w_uq": (2, P(None, MODEL)), "w_uk": (2, P(None, MODEL)),
    "w_q": (2, P(None, MODEL)),      # direct q projection (no q LoRA)
    "w_uv": (2, P(None, MODEL)),
    # dense mlp
    "wi": (2, P(None, MODEL)), "wg": (2, P(None, MODEL)),
    "wo2": (2, P(MODEL, None)),
    # mamba (replicated over model; batch-parallel only)
    "in_proj": (2, P()), "out_proj": (2, P()),
    # generic dense_init {'w': ...}
    "w": (2, P()),
}

_MOE = {
    "router": (2, P()),
    "wi": (3, P(MODEL, None, None)),
    "wg": (3, P(MODEL, None, None)),
    "wo": (3, P(MODEL, None, None)),
}


_FSDP_MIN_DIM = 1024  # don't FSDP-shard small dims


def _add_fsdp(spec, shape, fsdp_axes):
    """ZeRO-3/FSDP-in-GSPMD: also shard the largest unsharded dim over the
    data axes.  GSPMD inserts the per-layer all-gather inside the scan loop
    (the standard MaxText pattern); the shard_map MoE path receives weights
    via in_specs P('model',...) so jit re-gathers them there automatically.
    """
    if not fsdp_axes:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    cands = [i for i, (p, s) in enumerate(zip(parts, shape))
             if p is None and s >= _FSDP_MIN_DIM]
    if not cands:
        return spec
    i = max(cands, key=lambda i: shape[i])
    parts[i] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
    return P(*parts)


def _leaf_spec(key, leaf, in_moe, parent, fsdp_axes):
    if key == "w":
        # generic dense_init leaf: shard the LM head column-wise, keep the
        # small projections (mtp proj, etc.) replicated
        spec = P(None, MODEL) if parent == "head" else P()
        return _add_fsdp(spec, leaf.shape, fsdp_axes) \
            if parent == "head" else spec
    table = _MOE if in_moe and key in _MOE else _BASE
    if key not in table:
        return P()
    base_nd, spec = table[key]
    extra = leaf.ndim - base_nd
    if extra < 0:
        return P()
    full = P(*([None] * extra + list(spec)))
    if key in ("scale", "bias", "a_log", "D", "dt_bias", "conv_b", "conv_w",
               "pos_emb", "enc_pos",
               # embeddings: FSDP on the feature dim makes the token gather
               # unpartitionable (involuntary full remat in SPMD) — the
               # vocab-sharded table is small enough per device already
               "emb"):
        return full
    return _add_fsdp(full, leaf.shape, fsdp_axes)


def param_specs(params, cfg=None, fsdp_axes=(), kv_replicated=False):
    """``kv_replicated``: keep wk/wv whole on every model shard — the
    Megatron GQA fallback when n_kv_heads < tp_size, used by the explicit-TP
    stack (each device computes all KV heads and slices its group's one;
    models/attention.py)."""
    fsdp_axes = tuple(fsdp_axes)

    def walk(node, key=None, in_moe=False, parent=None):
        if isinstance(node, dict):
            moe_here = "router" in node
            return {k: walk(v, k,
                            # the shared expert is a plain dense MLP — do
                            # NOT apply expert sharding to its stack dim
                            (in_moe or moe_here) and k != "shared",
                            key)
                    for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, key, in_moe, parent) for v in node)
        if kv_replicated and key in ("wk", "wv"):
            return P()
        return _leaf_spec(key, node, in_moe, parent, fsdp_axes)
    return walk(params)


def state_specs(state, cfg=None, fsdp_axes=()):
    ps = param_specs(state["params"], cfg, fsdp_axes)
    return {"params": ps,
            "opt": {"m": ps, "v": ps, "count": P()}}


# --------------------------------------------------------------------------- #
def _div(n, size):
    return n % size == 0


def batch_spec(mesh):
    return P(data_axes_of(mesh))


def cache_specs(cfg, mesh, batch):
    """Decode-cache specs.  batch over data when divisible, else the KV
    sequence takes the data axes (long_500k, batch=1).  KV heads over
    ``model`` when divisible, else the sequence also takes ``model``
    (sequence-parallel decode attention — GSPMD inserts the partial-softmax
    combine)."""
    dax = data_axes_of(mesh)
    dsize = int(np.prod([mesh.shape[a] for a in dax]))
    msize = mesh.shape[MODEL]
    batch_ok = _div(batch, dsize)
    b_ax = dax if batch_ok else None
    s_data = None if batch_ok else dax

    def kv4(hkv):  # (B, S, Hkv, Dh)
        h_ax = MODEL if _div(hkv, msize) else None
        s_ax = s_data if h_ax else (
            (tuple(dax) + (MODEL,)) if s_data else MODEL)
        return P(b_ax, s_ax, h_ax, None)

    def lat3(_):   # (B, S, R) compressed latent (MLA) — no head dim
        s_ax = (tuple(dax) + (MODEL,)) if s_data else MODEL
        return P(b_ax, s_ax, None)

    def walk(node, key=None):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        nd = node.ndim
        if key in ("k", "v"):
            base = kv4(node.shape[-2])
            return P(*([None] * (nd - 4) + list(base)))
        if key in ("c", "kr"):
            base = lat3(None)
            return P(*([None] * (nd - 3) + list(base)))
        if key == "state":  # mamba (B, H, P, N)
            return P(*([None] * (nd - 4) + [b_ax, None, None, None]))
        if key == "conv":   # (B, K-1, C)
            return P(*([None] * (nd - 3) + [b_ax, None, None]))
        if key == "enc_out":
            return P(b_ax, None, None)
        return P()
    return walk


def shardings_for(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))
