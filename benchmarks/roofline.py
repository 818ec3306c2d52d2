"""Roofline derivation from dry-run artifacts (EXPERIMENTS.md §Roofline).

Terms (per device; post-SPMD HLO shapes are already per-device):
  compute_s    = dot_FLOPs_dev / PEAK_FLOPS
  memory_s     = HBM_bytes_dev / HBM_BW
  collective_s = sum_op payload_dev * alg_factor(op, group) / ICI_BW

Hardware: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) per training step;
for decode, D = tokens decoded per step (= batch).  The ratio
MODEL_FLOPS / (3 * dot_FLOPs_total) — fwd+bwd dot flops ~ 3x fwd — catches
remat/redundancy waste (reported as useful_fraction).
"""
from __future__ import annotations

import glob
import json
import os
import sys

from benchmarks import hlo_cost

PEAK_FLOPS = 197e12     # bf16 / chip
HBM_BW = 819e9          # bytes/s / chip
ICI_BW = 50e9           # bytes/s / link

_ALG_FACTOR = {
    "all-reduce": lambda n: 2 * (n - 1) / max(n, 1),
    "all-gather": lambda n: (n - 1) / max(n, 1),
    "reduce-scatter": lambda n: (n - 1) / max(n, 1),
    "all-to-all": lambda n: (n - 1) / max(n, 1),
    "collective-permute": lambda n: 1.0,
}


def roofline_terms(hlo_text, *, model_flops_per_device=None):
    r = hlo_cost.analyze(hlo_text)
    compute_s = r["flops"] / PEAK_FLOPS
    memory_s = r["hbm_bytes"] / HBM_BW
    coll_s = 0.0
    for op, d in r["collectives"].items():
        coll_s += d["bytes"] * _ALG_FACTOR[op](d["group"]) / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s,
             "hlo_flops_dev": r["flops"], "hbm_bytes_dev": r["hbm_bytes"],
             "collectives": r["collectives"]}
    terms["dominant"] = max(("compute_s", "memory_s", "collective_s"),
                            key=lambda k: terms[k])
    if model_flops_per_device:
        terms["model_flops_dev"] = model_flops_per_device
        terms["useful_fraction"] = (model_flops_per_device /
                                    max(r["flops"], 1.0))
    return terms


# --------------------------------------------------------------------------- #
def param_count(cfg):
    """Total / active param counts (approx, embeddings excluded from 6ND)."""
    d, L = cfg.d_model, cfg.n_layers
    if cfg.family == "ssm":
        d_in = cfg.ssm_expand * d
        per = d * (2 * d_in + 2 * cfg.ssm_state + d_in // cfg.ssm_head_dim) \
            + d_in * d
        return per * L, per * L
    Dh = cfg.resolved_head_dim
    attn = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * Dh \
        + cfg.n_heads * Dh * d
    if cfg.use_mla:
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        q_in = cfg.q_lora_rank or d      # no q LoRA: one direct projection
        attn = (d * cfg.q_lora_rank
                + q_in * cfg.n_heads * (dn + dr)
                + d * (cfg.kv_lora_rank + dr)
                + cfg.kv_lora_rank * cfg.n_heads * (dn + dv)
                + cfg.n_heads * dv * d)
    mlp_mult = 3 if cfg.mlp in ("swiglu", "geglu") else 2
    if cfg.n_experts:
        dense_ff = cfg.dense_d_ff or cfg.d_ff
        n_dense = cfg.first_dense_layers
        n_moe = L - n_dense
        moe_total = n_moe * (cfg.n_experts * mlp_mult * d * cfg.moe_d_ff
                             + cfg.n_shared_experts * mlp_mult * d * cfg.moe_d_ff)
        moe_active = n_moe * ((cfg.top_k + cfg.n_shared_experts)
                              * mlp_mult * d * cfg.moe_d_ff)
        total = L * attn + n_dense * mlp_mult * d * dense_ff + moe_total
        active = L * attn + n_dense * mlp_mult * d * dense_ff + moe_active
        return total, active
    per = attn + mlp_mult * d * cfg.d_ff
    if cfg.family == "hybrid":
        d_in = cfg.ssm_expand * d
        mamba_per = d * (2 * d_in + 2 * cfg.ssm_state
                         + d_in // cfg.ssm_head_dim) + d_in * d
        n_shared = L // max(cfg.attn_every, 1)
        total = L * mamba_per + (attn + mlp_mult * d * cfg.d_ff) \
            + n_shared * 2 * d * d
        return total, total
    n_layers = L + (cfg.n_enc_layers if cfg.is_encoder_decoder else 0)
    return n_layers * per, n_layers * per


def model_flops(cfg, shape_cfg, chips):
    """6*N_active*D per step, per device."""
    _, active = param_count(cfg)
    if shape_cfg.mode == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 6 * active * tokens / chips
    if shape_cfg.mode == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 2 * active * tokens / chips
    return 2 * active * shape_cfg.global_batch / chips  # decode: 1 tok/seq


def main():
    """Summarise every dry-run HLO in experiments/dryrun into a table."""
    sys.path.insert(0, "src")
    from repro.configs.base import INPUT_SHAPES, get_config
    out = []
    for hlo_path in sorted(glob.glob("experiments/dryrun/*.hlo")):
        tag = os.path.basename(hlo_path)[:-4]
        arch = shape = meshk = None
        for s in INPUT_SHAPES:
            if f"_{s}_" in tag:
                arch, rest = tag.split(f"_{s}_", 1)
                shape, meshk = s, rest.split("_")[0]
                break
        if shape is None or "_" in (meshk or "_"):
            continue  # connection-suffixed perf runs are analysed separately
        chips = 512 if meshk == "multi" else 256
        cfg = get_config(arch)
        mf = model_flops(cfg, INPUT_SHAPES[shape], chips)
        with open(hlo_path) as f:
            terms = roofline_terms(f.read(), model_flops_per_device=mf)
        row = {"arch": arch, "shape": shape, "mesh": meshk, **{
            k: terms[k] for k in ("compute_s", "memory_s", "collective_s",
                                  "dominant", "useful_fraction",
                                  "hlo_flops_dev")}}
        out.append(row)
        print(f"{arch:24s} {shape:12s} {meshk:6s} "
              f"C={terms['compute_s']*1e3:9.3f}ms "
              f"M={terms['memory_s']*1e3:9.3f}ms "
              f"N={terms['collective_s']*1e3:9.3f}ms "
              f"dom={terms['dominant'][:-2]:10s} "
              f"useful={terms.get('useful_fraction', 0):.2f}")
    with open("experiments/roofline.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
