"""Operations and bytes the algorithms need, from shapes alone.

Model FLOPs follow PaLM (Chowdhery et al. 2022, App. B): a token costs
``2 N`` FLOPs forward and ``6 N`` forward and backward, where ``N`` counts
the weights that enter a matrix multiplication (the blocks' projections
and the tied output head; the embedding and position lookups are
gathers), plus attention's ``2 * 2 * L * D * ctx`` forward (scores and the
weighted sum over ``ctx`` keys).  Training counts the full ``S``-key
attention of PaLM's ``12 L D S``; serving counts the causal context each
token really attends.  Recomputation is never counted.
"""
from __future__ import annotations


def matmul_params(sz: dict) -> int:
    """Weights that enter a matmul: per block ``4 D^2`` (q, k, v, o) and
    ``2 D F`` (MLP), and the ``V x D`` tied head."""
    L, D, F, V = sz["L"], sz["D"], sz["F"], sz["V"]
    return L * (4 * D * D + 2 * D * F) + V * D


def train_flops_per_token(sz: dict, seq: int) -> float:
    """``6 N + 12 L D S`` (PaLM App. B)."""
    return 6.0 * matmul_params(sz) + 12.0 * sz["L"] * sz["D"] * seq


def segment_ctx(segments) -> int:
    """Sum over a tick's live tokens of the keys each attends: a segment
    of ``n`` tokens from position ``p`` attends ``p+1 .. p+n`` keys."""
    return sum(n * p + n * (n + 1) // 2 for p, n in segments)


def serve_tick_flops(sz: dict, segments, emitted: int) -> float:
    """Forward FLOPs of one packed tick: the blocks over every live token,
    causal attention over each token's context, and the head over the
    ``emitted`` rows that yielded a token."""
    L, D, F, V = sz["L"], sz["D"], sz["F"], sz["V"]
    n_tok = sum(n for _, n in segments)
    blocks = 2.0 * L * (4 * D * D + 2 * D * F) * n_tok
    attn = 4.0 * L * D * segment_ctx(segments)
    return blocks + attn + 2.0 * D * V * emitted


def paged_attention_cost(sz: dict, segments, page: int, itemsize: int):
    """(FLOPs, bytes) of the packed paged-attention kernel over all layers
    for one tick: scores and weighted sum over each token's context, and
    each segment's pages of K and V read once, with the queries read and
    the outputs written once."""
    L, D = sz["L"], sz["D"]
    flops = 4.0 * L * D * segment_ctx(segments)
    pages = sum(-(-(p + n) // page) for p, n in segments)
    n_tok = sum(n for _, n in segments)
    kv = 2 * pages * page * D * itemsize
    qo = 2 * n_tok * D * itemsize
    return flops, float(L * (kv + qo))


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak bf16 FLOP/s and bytes over peak HBM bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
