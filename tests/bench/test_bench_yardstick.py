"""The benchmark's yardstick on the CPU: FLOP counts against the compiler's
own count, the paged-attention cost by hand, the traffic generator's
promises, and the interval arithmetic of the trace reduction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops
import traffic
import xtrace

SZ = {"L": 2, "D": 256, "H": 4, "F": 1024, "V": 1024, "S": 128,
      "eps": 1e-6}


def _cfg():
    from repro.configs.base import get_config
    return get_config("gpt2-774m").reduced().replace(
        connection="fal", d_model=SZ["D"], n_heads=SZ["H"],
        n_kv_heads=SZ["H"], head_dim=SZ["D"] // SZ["H"], d_ff=SZ["F"],
        vocab=SZ["V"], max_seq=SZ["S"], attn_block_q=SZ["S"],
        attn_block_k=SZ["S"])


def test_train_flops_match_the_compiled_step():
    """6N + 12 L D S per token against XLA's count of the program's
    forward and backward at the same widths (matmuls dominate; layer
    norms, GELU and softmax add the rest)."""
    from repro.models import model as M
    cfg = _cfg()
    B, S = 2, SZ["S"]
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    grad = jax.jit(jax.grad(lambda p, t: M.loss_fn(p, cfg,
                                                   {"tokens": t})[0]))
    cost = grad.lower(params, tokens).compile().cost_analysis()
    got = flops.train_flops_per_token(SZ, S) * B * S
    assert got == pytest.approx(cost["flops"], rel=0.15)


def test_serve_flops_match_a_forward():
    """2N per token plus causal attention against XLA's count of the
    reference's forward, which computes every score (counted as full
    S x S keys here)."""
    from repro.models import model as M
    cfg = _cfg()
    S = SZ["S"]
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, S), jnp.int32)
    fwd = jax.jit(lambda p, t: M.forward(p, cfg, {"tokens": t})[0])
    cost = fwd.lower(params, tokens).compile().cost_analysis()
    # every row through the head, every query against all S keys
    full = (flops.serve_tick_flops(SZ, [(0, S)], S)
            - 4.0 * SZ["L"] * SZ["D"] * flops.segment_ctx([(0, S)])
            + 4.0 * SZ["L"] * SZ["D"] * S * S)
    assert full == pytest.approx(cost["flops"], rel=0.15)


def test_paged_attention_cost_by_hand():
    sz = {"L": 3, "D": 64}
    # one decode token at position 20 (21 keys, pages of 16: 2 pages) and
    # a 4-token prefill segment from 0 (1+2+3+4 keys, 1 page)
    f, b = flops.paged_attention_cost(sz, [(20, 1), (0, 4)], 16, 2)
    assert f == 4 * 3 * 64 * (21 + 10)
    assert b == 3 * (2 * 3 * 16 * 64 * 2 + 2 * 5 * 64 * 2)
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert flops.roofline_s(f, b, peak) == max(f / 1e12, b / 1e9)


TR = {"rate_per_s": 5.0, "token_ids": 1000, "max_total": 300,
      "prompt_len": {"median": 64, "sigma": 0.8, "min": 8, "max": 200},
      "output_len": {"median": 32, "sigma": 0.7, "min": 4, "max": 100}}


def test_every_seed_asks_for_the_same_work_at_the_same_times():
    a = traffic.schedule(TR, 1, 20.0)
    b = traffic.schedule(TR, 2**40 + 1, 20.0)
    assert len(a) == len(b) == 100
    assert [(len(r.prompt), r.max_new, r.at_s) for r in a] == \
        [(len(r.prompt), r.max_new, r.at_s) for r in b]
    lens = [len(r.prompt) for r in a]
    assert lens != sorted(lens)                         # shuffled once
    gaps = lambda s: sorted(np.diff([r.at_s for r in s]).round(9))
    assert a[0].at_s == 0.0 and a[-1].at_s < 20.0
    assert not np.array_equal(a[1].prompt[:8], b[1].prompt[:8])
    again = traffic.schedule(TR, 1, 20.0)
    assert all(np.array_equal(x.prompt, y.prompt) and x.at_s == y.at_s
               for x, y in zip(a, again))
    assert all(len(r.prompt) + r.max_new <= TR["max_total"] for r in a)
    assert gaps(a)[-1] > 5 * gaps(a)[len(a) // 2]     # exponential tail


def test_shared_prefixes():
    tr = dict(TR, shared_prefix={"tokens": 16, "groups": 2})
    reqs = traffic.schedule(tr, 3, 20.0)
    heads = {tuple(r.prompt[:16]) for r in reqs if len(r.prompt) >= 16}
    assert len(heads) == 2


def test_steady_state_in_flight():
    """The requests in flight when the window opens: the same sizes for
    every seed, longest remaining output first, the long-lived ones drawn
    more often than the mix draws them, and each within the mix's limits."""
    tr = dict(TR, prime={"live": 40},
              engine={"prefill_chunk": 32})
    a = traffic.prime(tr, 1)
    b = traffic.prime(tr, 2**40 + 1)
    assert len(a) == 40 and [r.rid for r in a] == list(range(-1, -41, -1))
    assert [(len(r.prompt), r.max_new) for r in a] == \
        [(len(r.prompt), r.max_new) for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    rest = [r.max_new for r in a]
    assert rest == sorted(rest, reverse=True) and min(rest) >= 1
    assert all(len(r.prompt) + r.max_new <= TR["max_total"] for r in a)
    # a request lives about as long as its output: in flight, outputs
    # run longer than the mix's own
    mix = traffic.schedule(TR, 1, 20.0)
    served = [len(r.prompt) + r.max_new for r in a]
    assert np.mean(served) > np.mean([len(r.prompt) + r.max_new
                                      for r in mix])
    assert traffic.prime(TR, 1) == []


def test_interval_arithmetic():
    iv = np.array([[0, 2], [1, 3], [5, 6]], float)
    assert xtrace.length(xtrace.union(iv)) == 4
    # collectives [0,4] and [5,8] against compute [1,2] and [6,7]
    assert xtrace.subtract(np.array([[0, 4], [5, 8]], float),
                           np.array([[1, 2], [6, 7]], float)) == 5
    assert xtrace.subtract(np.array([[0, 1]], float),
                           np.zeros((0, 2))) == 1
    assert xtrace.op_name("%fusion.12 = bf16[2] fusion(x)") == "fusion.12"
    assert xtrace.is_collective("all-reduce-start.3")
    assert not xtrace.is_collective("fusion.3")
