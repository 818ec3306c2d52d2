"""FAL (First Attentions Last) — the paper's contribution as a composable
connection-mode module.

A transformer block is ``x + MHA(ln1(x)) + MLP(mlp_input)``; the paper's whole
technique is the choice of ``mlp_input``:

  preln     : ln2(x + a)                      -- baseline GPT (eq 1); MLP needs the
                                                 *complete* attention output -> TP
                                                 all-reduce between MHA and MLP
  parallel  : ln2(x)                          -- PaLM/GPT-J baseline; no dependency
  fal       : ln2(x) + ln_a(a1)               -- eq (2); a1 = first block's MHA out,
                                                 ln_a owned by block 1 (footnote 3)
  falplus   : ln2(x + a) + ln_fal_i(a1)       -- eq (7); per-block ln_fal, keeps the
                                                 direct connection (quality variant)
  ablation1 : ln2(x) + ln_fal_i(a)            -- Apdx D.1: latest attention in the
                                                 LN+LN form (shown worse than preln)
  ablation2 : block0 preln, later blocks MLP(ln2(x)) with no alternative signal
                                                 (Apdx D.1: ~baseline, worse than FAL)

``mlp_input_depends_on_local_attention(mode)`` is the property the TP runtime
keys on: when False, the block's MHA partial sum never needs to be assembled
before the MLP, so the per-block MHA all-reduce is fused into the MLP one
(2 -> 1 collectives per block).  Since the toy-stack retirement this predicate
drives the REAL model: ``models/blocks.py::block_apply`` consumes it (via
``attention_must_assemble``) to choose between the two-psum assembled path and
the paper's fused single-psum path whenever it runs inside the
``models/model.py::decoder_stack_tp`` shard_map; the replicated single-device
path is the same code with the assemble reduced over nothing (tp_size = 1).
"""
from __future__ import annotations

from repro.models import layers as L
from repro.obs.trace import layer_scope

# modes whose MLP input requires the *assembled* (post all-reduce) attention
# output of the SAME block:
_NEEDS_LOCAL_ATTN = {"preln": True, "parallel": False, "fal": False,
                     "falplus": True, "ablation1": True, "ablation2": False}

# modes with a per-block LN over the injected signal:
NEEDS_LN_FAL = {"falplus", "ablation1"}
# modes that consume the first block's attention output:
USES_FIRST_ATTENTION = {"fal", "falplus"}

#: modes whose steady-state MLP input is independent of the block's OWN
#: attention output — the property the decode-time MHA||MLP dual-branch
#: dispatch keys on (``ExecutionPlan(dual_branch=True)``): both branches can
#: be issued concurrently because the MLP reads only the residual stream and
#: the cached first-attention signal, never this block's KV gather.
DUAL_BRANCH_MODES = tuple(m for m, dep in _NEEDS_LOCAL_ATTN.items()
                          if not dep)  # ('parallel', 'fal', 'ablation2')


def mlp_input_depends_on_local_attention(mode: str) -> bool:
    return _NEEDS_LOCAL_ATTN[mode]


def attention_must_assemble(mode: str, is_block0: bool = False) -> bool:
    """True when the block's own MHA output must be fully assembled (post
    TP all-reduce) before its MLP input / signal export can be formed.

    Steady-state blocks: exactly ``mlp_input_depends_on_local_attention``.
    Block 0 additionally assembles for ``fal`` (it exports the LN'd
    first-attention signal — the single extra all-reduce of Fig 2, paid once
    for the whole depth) and for ``ablation2`` (its eq-4 direct connection);
    only ``parallel`` keeps block 0 fused.
    """
    if is_block0:
        return mode != "parallel"
    return _NEEDS_LOCAL_ATTN[mode]


def first_attention_signal(cfg, block0_params, a1_raw):
    """What block 1 exports to the rest of the depth.

    FAL: normalize ONCE in block 1 (``ln_a``, the repositioned LN of
    footnote 3) so later blocks reuse the cached tensor with zero recompute.
    FAL+: export the raw tensor; each block applies its own ``ln_fal``.
    """
    if cfg.connection == "fal":
        with layer_scope("fal_signal"):
            return L.norm_apply(block0_params["ln_a"], a1_raw, cfg.norm)
    if cfg.connection == "falplus":
        return a1_raw
    return None


def mlp_input(cfg, p, x, a, a1_sig, norm_kind=None):
    """Compute the MLP input for one block given mode; see module docstring.

    p: block params (ln2 always; ln_fal for falplus/ablation1).
    x: block input (residual stream);  a: this block's MHA output;
    a1_sig: output of ``first_attention_signal`` (None unless fal/falplus).
    """
    nk = norm_kind or cfg.norm
    mode = cfg.connection
    if mode == "preln":
        return L.norm_apply(p["ln2"], x + a, nk)
    if mode == "parallel" or mode == "ablation2":
        return L.norm_apply(p["ln2"], x, nk)
    if mode == "fal":
        return L.norm_apply(p["ln2"], x, nk) + a1_sig.astype(x.dtype)
    if mode == "falplus":
        return (L.norm_apply(p["ln2"], x + a, nk)
                + L.norm_apply(p["ln_fal"], a1_sig, nk).astype(x.dtype))
    if mode == "ablation1":
        return (L.norm_apply(p["ln2"], x, nk)
                + L.norm_apply(p["ln_fal"], a, nk))
    raise ValueError(mode)


def block0_mlp_input(cfg, p, x, a, norm_kind=None):
    """Block 1 ("preparation stage").  For FAL the repositioned ``ln_a`` is
    applied to the MHA output and the same tensor feeds block 1's own MLP:
    ``ln2(x) + ln_a(a)`` (eq 2 with i=1).  For ablation2 block 1 keeps its
    direct connection (eq 4).  Other modes behave as in later blocks."""
    nk = norm_kind or cfg.norm
    mode = cfg.connection
    if mode == "fal":
        return L.norm_apply(p["ln2"], x, nk) + L.norm_apply(p["ln_a"], a, nk)
    if mode == "ablation2":
        return L.norm_apply(p["ln2"], x + a, nk)
    if mode == "falplus":
        # eq (7) i=1 branch: LN(X_1 + MHA_1)  (no ln_fal on itself)
        return L.norm_apply(p["ln2"], x + a, nk)
    return mlp_input(cfg, p, x, a, None, nk) if mode in ("preln", "parallel") \
        else mlp_input(cfg, p, x, a, a, nk)
