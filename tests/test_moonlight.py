"""Moonlight-16B-A3B (the DeepSeek-V3 block) with FAL at a small size on the
CPU, against the plain float32 reference of the benchmark
(``bench/configs/moonlight_fal_ref.py``): sigmoid routing with a selection
bias, the held expert share (dropless, its shares summing to the uncut
layer), MLA without a q LoRA, the train loss and gradients, and the
``PagedEngine`` path.

Both sides compute in float32 here, so the tolerances only absorb the
different order of float32 sums (XLA's fusions against the reference's
einsums); each states its own reason.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.plan import ExecutionPlan
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as M
from repro.models import moe as MOE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_ref():
    path = os.path.join(REPO, "bench", "configs", "moonlight_fal_ref.py")
    spec = importlib.util.spec_from_file_location("moonlight_fal_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_ref()

#: 1 dense + 1 MoE layer, 16 experts of which 4 are held, top 3
CFG = get_config("moonlight-16b-a3b").reduced().replace(
    d_model=64, n_heads=4, n_kv_heads=4, d_ff=96, vocab=256, max_seq=64,
    n_experts=16, experts_held=4, top_k=3, moe_d_ff=32, n_shared_experts=2,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, dtype="float32", param_dtype="float32")


def _conf(cfg):
    """The configuration file's keys (as ``sizes`` reads them) for cfg."""
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "intermediate_size": cfg.d_ff,
            "moe_intermediate_size": cfg.moe_d_ff,
            "n_routed_experts_published": cfg.n_experts,
            "n_routed_experts": cfg.experts_held or cfg.n_experts,
            "num_experts_per_tok": cfg.top_k,
            "n_shared_experts": cfg.n_shared_experts,
            "first_k_dense_replace": cfg.first_dense_layers,
            "routed_scaling_factor": cfg.routed_scale,
            "router_aux_coef": cfg.router_aux_coef,
            "vocab_size": cfg.vocab, "max_position_embeddings": cfg.max_seq,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": 1e-6}


def _params(cfg, seed=0, bias_std=0.1):
    """Seeded weights; the selection bias non-zero, so that choosing and
    weighting read different numbers."""
    p = M.init_params(jax.random.PRNGKey(seed), cfg)
    b = p["blocks_moe"]["ffn"]["e_bias"]
    p["blocks_moe"]["ffn"]["e_bias"] = bias_std * jax.random.normal(
        jax.random.PRNGKey(seed + 100), b.shape)
    return p


def _tokens(cfg, seed=1, shape=(2, 32)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, cfg.vocab)


def _layer(p, i=0):
    """Layer ``i`` of the stacked MoE blocks."""
    return jax.tree.map(lambda x: x[i], p["blocks_moe"])


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


# ---------------------------------------------------------------- routing
def test_sigmoid_bias_routing_against_a_plain_loop():
    """Choice by top-k of score + bias, weights the chosen scores
    renormalised times routed_scale, the balance loss over the
    normalised scores; against numpy one token at a time."""
    cfg = CFG
    ffn = _layer(_params(cfg))["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.d_model))
    w, e, aux = MOE._route(ffn, cfg, x)
    logits = np.asarray(x, np.float64) @ np.asarray(ffn["router"],
                                                    np.float64)
    scores = 1 / (1 + np.exp(-logits))
    bias = np.asarray(ffn["e_bias"], np.float64)
    E, k = cfg.n_experts, cfg.top_k
    picks = np.zeros(E)
    for t in range(len(x)):
        chosen = np.argsort(-(scores[t] + bias))[:k]
        gate = scores[t, chosen] / scores[t, chosen].sum() * cfg.routed_scale
        assert sorted(np.asarray(e[t])) == sorted(chosen)
        got = dict(zip(np.asarray(e[t]), np.asarray(w[t])))
        # float32 against float64 gates of order 1
        np.testing.assert_allclose([got[c] for c in chosen], gate,
                                   rtol=1e-5)
        picks[chosen] += 1
    P = (scores / scores.sum(1, keepdims=True)).mean(0)
    np.testing.assert_allclose(float(aux), E * np.sum(picks / len(x) * P),
                               rtol=1e-5)
    # the bias really moved the choice somewhere (else the test is weak)
    plain = np.argsort(-scores, 1)[:, :k]
    assert any(set(plain[t]) != set(np.asarray(e[t])) for t in range(len(x)))


def test_selection_bias_gets_no_gradient():
    cfg = CFG
    p = _params(cfg)
    g = jax.grad(lambda q: M.loss_fn(q, cfg, {"tokens": _tokens(cfg)})[0])(p)
    assert float(jnp.abs(g["blocks_moe"]["ffn"]["e_bias"]).max()) == 0.0
    assert float(jnp.abs(g["blocks_moe"]["ffn"]["router"]).max()) > 0.0


def test_softmax_routing_stays_the_default():
    base = get_config("qwen3-moe-30b-a3b")
    assert base.router_score == "softmax" and base.routed_scale == 1.0
    p = M.init_params(jax.random.PRNGKey(0), base.reduced())
    assert not any("e_bias" in jax.tree_util.keystr(k)
                   for k, _ in jax.tree_util.tree_flatten_with_path(p)[0])


# ------------------------------------------------------------ held share
def _share(ffn, cfg, off, n):
    """The share of experts off .. off + n: its parameters and config (the
    offset goes to ``moe_apply_held`` itself)."""
    p = {k: ffn[k] for k in ("router", "e_bias", "shared")}
    p.update({k: ffn[k][off:off + n] for k in ("wi", "wg", "wo")})
    return p, cfg.replace(experts_held=n)


def test_the_four_shares_sum_to_the_uncut_layer():
    """Each share computes its experts' part for every pair routed to
    them, and the shared experts, which every chip computes alike for its
    own tokens: summed with the shared experts counted once, against the
    reference's plain loop over all 16 experts, and the program's
    capacity path with room for every pair."""
    cfg = CFG.replace(experts_held=0)
    ffn = _layer(_params(cfg))["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.d_model))
    shared = L.mlp_apply(ffn["shared"], x, "swiglu")
    total, pairs = shared, 0
    for off in range(0, 16, 4):
        p, c = _share(ffn, cfg, off, 4)
        y, (_, n) = MOE.moe_apply_held(p, c, x, offset=off)
        total = total + (y - shared)
        pairs += int(n.sum())
    assert pairs == x.shape[0] * x.shape[1] * cfg.top_k
    sz = REF.sizes(_conf(cfg))
    ref, _ = REF._experts(ffn, x, sz, "f32")
    # float32 sums in another order; outputs of order 0.1-1
    np.testing.assert_allclose(total, ref, atol=2e-5, rtol=1e-4)
    cap, _ = MOE.moe_apply(ffn, cfg.replace(capacity_factor=16.0), x)
    np.testing.assert_allclose(total, cap, atol=2e-5, rtol=1e-4)


def test_held_layer_against_the_reference_share():
    """One held share (experts 8..11) against the reference given the same
    share, and the pair counts against the routing."""
    cfg = CFG
    full = _layer(_params(CFG.replace(experts_held=0)))["ffn"]
    p, _ = _share(full, cfg, 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    y, (aux, pairs) = MOE.moe_apply_held(p, cfg, x, offset=8)
    ref, ref_aux = REF._experts(p, x, REF.sizes(_conf(cfg)), "f32",
                                offset=8)
    np.testing.assert_allclose(y, ref, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-5)
    _, e, _ = MOE._route(p, cfg, x.reshape(-1, cfg.d_model))
    want = [(np.asarray(e) == 8 + j).sum() for j in range(4)]
    assert list(np.asarray(pairs)) == want


@pytest.mark.parametrize("hot", [False, True])
def test_dropless_a_token_does_not_see_its_batch(hot):
    """A token's output is the same whatever the other tokens of its batch
    are; ``hot`` routes every token to held expert 0, where a capacity
    buffer would overflow and drop."""
    cfg = CFG
    ffn = _layer(_params(cfg))["ffn"]
    if hot:
        ffn["e_bias"] = ffn["e_bias"].at[0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 48, cfg.d_model))
    other = x.at[:, 1:].set(jax.random.normal(jax.random.PRNGKey(7),
                                              (1, 47, cfg.d_model)))
    alone, _ = MOE.moe_apply_held(ffn, cfg, x[:, :1])
    for batch in (x, other):
        y, (_, pairs) = MOE.moe_apply_held(ffn, cfg, batch)
        # the same float32 row products, in grouped matmuls of other sizes
        np.testing.assert_allclose(y[:, :1], alone, atol=1e-5, rtol=1e-5)
        if hot:
            assert int(pairs[0]) == batch.shape[1]
    if hot:
        # the capacity path at its default factor drops some of them
        full = dict(ffn)
        full.update({k: jnp.concatenate([ffn[k]] * 4) for k in
                     ("wi", "wg", "wo")})
        cap, _ = MOE.moe_apply(full, cfg.replace(experts_held=0,
                                                 capacity_factor=1.0), x)
        held, _ = MOE.moe_apply_held(full, cfg.replace(experts_held=0), x)
        assert not np.allclose(cap, held, atol=1e-3)


def test_pair_counters_in_the_train_metrics():
    """The train step's metrics give, per MoE layer, the pairs routed to
    each held expert and the held share of all pairs."""
    from repro.optim import adamw
    from repro.train import step as tstep
    cfg = CFG.replace(n_layers=3)
    ocfg = adamw.AdamWConfig()
    st = tstep.init_state(jax.random.PRNGKey(0), cfg, ocfg)
    toks = _tokens(cfg)
    _, met = jax.jit(tstep.make_train_step(cfg, ocfg))(st, {"tokens": toks})
    pairs, share = np.asarray(met["expert_pairs"]), np.asarray(
        met["held_share"])
    assert pairs.shape == (2, 4) and share.shape == (2,)
    np.testing.assert_allclose(share, pairs.sum(1) / (toks.size * 3),
                               rtol=1e-6)
    assert 0 < share.min() and share.max() < 1


def test_single_device_plans_only():
    from repro.launch import mesh as MX
    mesh = MX.make_mesh((1, 1), ("data", "model"),
                        devices=jax.devices()[:1])
    for tp in ("gspmd", "explicit"):
        with pytest.raises(ValueError, match="experts_held"):
            ExecutionPlan.from_mesh(mesh, tp=tp).validate(CFG)
        ExecutionPlan.from_mesh(mesh, tp=tp).validate(
            CFG.replace(experts_held=0))
    ExecutionPlan.single_device().validate(CFG)


# -------------------------------------------------------------------- MLA
def test_mla_without_a_q_lora():
    """q_lora_rank 0: one direct q projection, no w_dq/q_norm/w_uq; the
    layer against the reference's MLA, and decoding token by token
    through the latent cache against the full sequence."""
    cfg = CFG
    p = A.mla_init(jax.random.PRNGKey(0), cfg)
    assert "w_q" in p and not {"w_dq", "q_norm", "w_uq"} & set(p)
    assert p["w_q"].shape == (cfg.d_model, cfg.n_heads * 24)
    S = 32
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    y = A.mla_apply(p, cfg, x, pos)
    ref = REF._attention(p, x, REF.sizes(_conf(cfg)), "f32")
    np.testing.assert_allclose(y, ref, atol=2e-5, rtol=1e-4)
    cache = A.mla_init_cache(cfg, 2, S, jnp.float32)
    steps = []
    for t in range(S):
        o, cache = A.mla_decode(p, cfg, x[:, t:t + 1], cache,
                                jnp.full((2,), t, jnp.int32))
        steps.append(o)
    # the absorbed decode sums the latent products in another order
    np.testing.assert_allclose(jnp.concatenate(steps, 1), y, atol=2e-5,
                               rtol=1e-4)
    with_lora = A.mla_init(jax.random.PRNGKey(0), cfg.replace(q_lora_rank=24))
    assert {"w_dq", "q_norm", "w_uq"} <= set(with_lora)
    assert "w_q" not in with_lora


# ------------------------------------------------------ the whole model
def test_logits_loss_and_gradients_against_the_reference():
    cfg = CFG
    p = _params(cfg)
    toks = _tokens(cfg)
    sz = REF.sizes(_conf(cfg))
    logits, _, _ = M.forward(p, cfg, {"tokens": toks})
    ref_logits = REF.logits(p, toks, sz)
    # float32 on both sides: logits of order 1, summed in another order
    np.testing.assert_allclose(logits, ref_logits, atol=1e-4, rtol=1e-4)
    (loss, _), g = jax.value_and_grad(
        lambda q: M.loss_fn(q, cfg, {"tokens": toks}), has_aux=True)(p)
    ref_loss, ref_g = jax.value_and_grad(
        lambda q: REF.loss(q, toks, sz))(p)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(ref_g)[0]
    got = dict(jax.tree_util.tree_flatten_with_path(g)[0])
    for path, r in flat:
        if float(jnp.abs(r).max()) == 0.0:      # the selection bias
            assert float(jnp.abs(got[path]).max()) == 0.0
            continue
        # per leaf, relative to its own norm: float32 accumulations
        assert _rel(got[path], r) < 1e-3, jax.tree_util.keystr(path)


def test_paged_engine_prefill_and_decode_against_the_reference():
    """Two requests prefilled in chunks and decoded greedily through
    ``PagedEngine``, in packed ticks together; every logits row a tick
    produced against the reference's full forward over the same tokens,
    at that position."""
    from repro.serve.scheduler import EngineConfig, PagedEngine, \
        ServeRequest
    cfg = CFG
    p = _params(cfg)
    eng = PagedEngine(cfg, p, EngineConfig(page_size=8, num_pages=32,
                                           slots=2, prefill_chunk=8,
                                           max_seq=64))
    rows = []
    orig = eng._step_for

    def recording(fast):
        fn = orig(fast)

        def step(params, cache, tokens, tok_slot, tok_pos, bt, seg_last,
                 *rest):
            out = fn(params, cache, tokens, tok_slot, tok_pos, bt,
                     seg_last, *rest)
            pos = np.asarray(tok_pos)
            for i, t in enumerate(np.asarray(seg_last)):
                if t >= 0:
                    rows.append((eng.slots[i].rid, int(pos[t]),
                                 np.asarray(out[0][i])))
            return out
        return step

    eng._step_for = recording
    reqs = [ServeRequest(rid=s, prompt=np.asarray(
        _tokens(cfg, seed=s, shape=(n,))), max_new=6)
        for s, n in ((11, 19), (12, 5))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    sz = REF.sizes(_conf(cfg))
    for r in reqs:
        assert r.done and len(r.generated) == 6
        full = np.asarray(r.known(), np.int32)[None]
        ref = np.asarray(REF.logits(p, full, sz))[0]
        got = {pos: row for rid, pos, row in rows if rid == r.rid}
        # every prompt chunk's last position and every decode step
        assert set(range(len(r.prompt) - 1, full.shape[1] - 1)) <= set(got)
        for pos, row in got.items():
            # float32 on both sides; the packed tick gathers the latent
            # cache and sums in another order: logits of order 1
            np.testing.assert_allclose(row, ref[pos], atol=1e-4, rtol=1e-4)
            if pos >= len(r.prompt) - 1:
                assert r.known()[pos + 1] == int(np.argmax(row))
