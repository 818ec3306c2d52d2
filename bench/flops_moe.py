"""Operations and bytes of the DeepSeek-V3 block (MLA, a leading dense
layer, shared and routed experts) on one chip's expert share, from shapes
alone; the sizes are ``moonlight_fal_ref.sizes``'s.

Model FLOPs follow PaLM (Chowdhery et al. 2022, App. B) as
``bench/flops.py`` does: ``6 N`` a token forward and backward, ``N`` the
weights that enter a matrix multiplication for that token, plus attention
over the full ``S`` keys: ``6 L S H (d_qk + d_v)`` (scores over the
``dn + dr`` query/key dimensions and the weighted sum over ``dv``).  A
token enters its ``k`` routed experts, of which ``k * held / E`` are held
here on average; the experts other chips hold are left out, as in the
program.  Recomputation is never counted.
"""
from __future__ import annotations

#: bytes of an operand of the grouped products (bf16 compute)
ITEMSIZE = 2


def attn_params(sz: dict) -> int:
    """MLA without a q LoRA: q (D x H(dn+dr)), the kv down-projection and
    the shared k_rope head (D x (R+dr)), the kv up-projections
    (R x H(dn+dv)) and the output (H dv x D)."""
    D, H, R = sz["D"], sz["H"], sz["R"]
    dn, dr, dv = sz["dn"], sz["dr"], sz["dv"]
    return D * H * (dn + dr) + D * (R + dr) + R * H * (dn + dv) + H * dv * D


def expert_params(sz: dict) -> int:
    """One SwiGLU expert: wi, wg (D x Fe) and wo (Fe x D)."""
    return 3 * sz["D"] * sz["Fe"]


def held_pairs_per_token(sz: dict) -> float:
    """Routed experts a token enters on this chip, on average."""
    return sz["k"] * sz["held"] / sz["E"]


def active_params(sz: dict) -> float:
    """Weights a token multiplies: every layer's attention, the dense
    layers' FFN, each MoE layer's router, shared experts and its held
    routed pairs, and the untied head (the embedding is a gather)."""
    L, dense, D = sz["L"], sz["dense"], sz["D"]
    moe = sz["E"] * D + (sz["shared"] + held_pairs_per_token(sz)) \
        * expert_params(sz)
    return (L * attn_params(sz) + dense * 3 * D * sz["F"]
            + (L - dense) * moe + D * sz["V"])


def train_flops_per_token(sz: dict, seq: int) -> float:
    """``6 N_active + 6 L S H (d_qk + d_v)``."""
    return (6.0 * active_params(sz)
            + 6.0 * sz["L"] * seq * sz["H"] * (sz["dn"] + sz["dr"] + sz["dv"]))


def expected_pairs(sz: dict, tokens: int) -> float:
    """(token, expert) pairs a MoE layer routes to the held experts, on
    average, for ``tokens`` tokens."""
    return tokens * held_pairs_per_token(sz)


def experts_cost(sz: dict, pairs: float):
    """(FLOPs, bytes) of the held experts' grouped products over all MoE
    layers, forward and backward, at ``pairs`` pairs a layer: three
    products (wi, wg: D -> Fe; wo: Fe -> D), each run forward, for the
    rows' gradient and for the weights' gradient; every such product
    reads or writes its rows in and out once and one expert-stacked
    weight (or weight gradient)."""
    D, Fe, n = sz["D"], sz["Fe"], sz["held"]
    per_layer_flops = 3 * 3 * 2.0 * pairs * D * Fe
    per_layer_elems = 3 * 3 * (pairs * (D + Fe) + n * D * Fe)
    layers = sz["L"] - sz["dense"]
    return layers * per_layer_flops, layers * per_layer_elems * ITEMSIZE
