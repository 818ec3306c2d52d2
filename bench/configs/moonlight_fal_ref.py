"""Plain reference of Moonlight-16B-A3B (the DeepSeek-V3 block) with the FAL
connection, in float32 ``jax.numpy``, on one chip's expert share.

It follows the published architecture and the sizes of a configuration
file beside it, and imports nothing of the program.  Parameters come as the
benchmark makes them (``bench/weights.py``), in the program's tree layout:
``embed.emb`` (V, D); ``block0`` (the dense layer) and the stacked
``blocks_moe`` (L-1, ...), each with RMSNorms ``ln1``, ``ln2`` (and
``ln_a`` in block 0) holding ``scale``, ``attn`` = MLA (``w_q`` D x H(dn+dr),
``w_dkv`` D x R, ``kv_norm``, ``w_kr`` D x dr, ``w_uk`` R x H dn, ``w_uv``
R x H dv, ``wo`` H dv x D) and ``ffn``: block 0 a SwiGLU MLP (``wi``,
``wg`` D x F, ``wo`` F x D), the others the router (``router`` D x E,
``e_bias`` E), the held experts (``wi``, ``wg`` n x D x Fe, ``wo``
n x Fe x D) and the shared experts (one SwiGLU MLP of width
``n_shared * Fe``); ``final_norm`` and the untied ``head.w`` (D, V).

A block is ``x + MLA(ln1(x)) + FFN(m)`` where block 1 exports
``sig = ln_a(MLA_1)`` and every block's FFN reads ``m = ln2(x) + sig``
(FAL, eq. 2; block 1 reads its own signal).

* Attention: MLA in its expanded form (k and v from the latent), no q LoRA
  (``q_lora_rank`` null), RoPE on the ``dr`` rope dimensions of q and of the
  one shared k_rope head, scores over ``dn + dr``, causal.  A block of
  queries at a time, each block recomputed in backward.
* Experts: sigmoid scores of the router; the top ``k`` of scores plus the
  selection bias ``e_bias`` choose (the bias gets no gradient); the gates
  are the chosen scores, normalised, times ``routed_scaling_factor``.  The
  held experts, the first ``n`` of ``E`` (``_experts`` takes another
  ``offset``), each add their gated
  output for the tokens that chose them (a plain loop with one-hot masks);
  what the other chips' experts would add is left out.  The shared experts
  see every token.  Balance loss: ``E * sum_e f_e P_e`` per MoE layer, f
  the share of each expert's picks over the layer's tokens and P the mean
  of the scores normalised over the experts, times ``router_aux_coef``,
  added to the loss.

Departures from the published model (noted in the configuration file):
FAL's ``ln2(x) + ln_a(a1)`` in place of the residual MLP input; the
selection bias is seeded and fixed (it is updated outside the gradient in
DeepSeek-V3's training); RMSNorm eps; RoPE pairs the rope dimensions as
halves (a fixed permutation of ``w_q``'s and ``w_kr``'s rope columns
against the published interleaved pairs).

``prec`` selects the matmul precision: ``"f32"`` is the reference
(``highest``); ``"fp8"`` rounds both operands of every matmul, and in
training the cotangent too, to float8 e4m3 with a per-tensor scale, which
is the control that one step below the program's bfloat16 must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0
#: queries per attention block (the sequence is cut into such blocks)
BLOCK_Q = 512


def sizes(conf: dict) -> dict:
    """The numbers the reference needs, read from a configuration file."""
    return {"L": conf["num_hidden_layers"], "D": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "dn": conf["qk_nope_head_dim"], "dr": conf["qk_rope_head_dim"],
            "dv": conf["v_head_dim"], "R": conf["kv_lora_rank"],
            "F": conf["intermediate_size"],
            "Fe": conf["moe_intermediate_size"],
            "E": conf["n_routed_experts_published"],
            "held": conf["n_routed_experts"],
            "k": conf["num_experts_per_tok"],
            "shared": conf["n_shared_experts"],
            "dense": conf["first_k_dense_replace"],
            "scale": conf["routed_scaling_factor"],
            "aux_coef": conf["router_aux_coef"],
            "V": conf["vocab_size"], "S": conf["max_position_embeddings"],
            "theta": float(conf["rope_theta"]), "eps": conf["rms_norm_eps"]}


def _q8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return jnp.einsum(spec, _q8(a), _q8(b))


def _fp8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return jnp.einsum(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y), *res)
    return vjp(_q8(g))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(spec, a, b, prec):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        return _fp8_einsum(spec, a, b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(p, x, eps):
    x = x.astype(jnp.float32)
    return (x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
            * p["scale"].astype(jnp.float32))


def _rope(x, theta):
    """RoPE over the last dimension of x (B, S, ..., dr) at positions
    0..S-1, the two halves of the dimension paired."""
    S, half = x.shape[1], x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = np.arange(S, dtype=np.float32)[:, None] * inv          # (S, half)
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, sz, prec):
    """MLA, expanded, causal; a block of queries at a time."""
    B, S, _ = h.shape
    H, dn, dr, dv = sz["H"], sz["dn"], sz["dr"], sz["dv"]
    q = _mm("bsd,de->bse", h, p["w_q"], prec).reshape(B, S, H, dn + dr)
    qn, qr = q[..., :dn], _rope(q[..., dn:], sz["theta"])
    c = _rms(p["kv_norm"], _mm("bsd,dr->bsr", h, p["w_dkv"], prec),
             sz["eps"])
    kr = _rope(_mm("bsd,de->bse", h, p["w_kr"], prec), sz["theta"])
    kn = _mm("bsr,re->bse", c, p["w_uk"], prec).reshape(B, S, H, dn)
    v = _mm("bsr,re->bse", c, p["w_uv"], prec).reshape(B, S, H, dv)
    bq = min(BLOCK_Q, S)
    assert S % bq == 0, (S, bq)

    @jax.checkpoint
    def block(i):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, i * bq, bq, 1)
        s = (_mm("bqhd,bkhd->bhqk", sl(qn), kn, prec)
             + _mm("bqhd,bkd->bhqk", sl(qr), kr, prec)) / np.sqrt(dn + dr)
        causal = (jnp.arange(S)[None, :]
                  <= i * bq + jnp.arange(bq)[:, None])
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", w, v, prec)            # (B, bq, H, dv)

    o = jax.lax.map(block, jnp.arange(S // bq))             # (n, B, bq, ...)
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, S, H * dv)
    return _mm("bse,ed->bsd", o, p["wo"], prec)


def _swiglu(p, m, prec):
    h = (jax.nn.silu(_mm("...d,df->...f", m, p["wg"], prec))
         * _mm("...d,df->...f", m, p["wi"], prec))
    return _mm("...f,fd->...d", h, p["wo"], prec)


def _experts(p, m, sz, prec, offset=0):
    """The held experts' part (experts ``offset .. offset + held``) and the
    shared experts of an MoE layer, and the layer's balance loss."""
    E, k = sz["E"], sz["k"]
    m2 = m.reshape(-1, m.shape[-1])
    scores = jax.nn.sigmoid(_mm("td,de->te", m2, p["router"], prec))
    sel = scores + jax.lax.stop_gradient(p["e_bias"].astype(jnp.float32))
    _, idx = jax.lax.top_k(sel, k)                           # (T, k)
    gate = jnp.take_along_axis(scores, idx, -1)
    gate = gate / jnp.sum(gate, -1, keepdims=True) * sz["scale"]
    picks = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)  # (T, E)
    aux = E * jnp.sum(jnp.mean(picks, 0) * jnp.mean(
        scores / jnp.sum(scores, -1, keepdims=True), 0))
    y = _swiglu(p["shared"], m2, prec)
    for j in range(sz["held"]):
        g = jnp.sum(jnp.where(idx == offset + j, gate, 0.0), -1)
        ex = {w: p[w][j] for w in ("wi", "wg", "wo")}
        y = y + g[:, None] * _swiglu(ex, m2, prec)
    return y.reshape(m.shape), aux


def hidden(params, tokens, sz, prec="f32"):
    """(final hidden states (B, S, D), summed balance loss) of token ids
    (B, S)."""
    eps = sz["eps"]
    x = params["embed"]["emb"].astype(jnp.float32)[tokens]
    b0 = params["block0"]
    a1 = _attention(b0["attn"], _rms(b0["ln1"], x, eps), sz, prec)
    sig = _rms(b0["ln_a"], a1, eps)
    x = x + a1 + _swiglu(b0["ffn"], _rms(b0["ln2"], x, eps) + sig, prec)

    @jax.checkpoint
    def block(carry, bp):
        x, aux = carry
        a = _attention(bp["attn"], _rms(bp["ln1"], x, eps), sz, prec)
        y, aux_l = _experts(bp["ffn"], _rms(bp["ln2"], x, eps) + sig, sz,
                            prec)
        return (x + a + y, aux + aux_l), None

    (x, aux), _ = jax.lax.scan(block, (x, jnp.float32(0)),
                               params["blocks_moe"])
    return _rms(params["final_norm"], x, eps), aux


def logits(params, tokens, sz, prec="f32"):
    """Logits (B, S, V) of token ids (B, S), over the vocabulary slice."""
    h, _ = hidden(params, tokens, sz, prec)
    return _mm("bsd,dv->bsv", h, params["head"]["w"], prec)


def loss(params, tokens, sz, prec="f32"):
    """Mean next-token cross-entropy over (B, S-1) positions plus the
    balance loss times its coefficient, as the program trains."""
    h, aux = hidden(params, tokens, sz, prec)
    lg = _mm("bsd,dv->bsv", h, params["head"]["w"], prec)[:, :-1]
    lse = jax.nn.logsumexp(lg, -1)
    tgt = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - tgt) + sz["aux_coef"] * aux


# --------------------------------------------------------------------- #
# training: AdamW steps over the whole batch of each step
# --------------------------------------------------------------------- #
def _frozen(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def make_train_fns(sz, opt: dict, prec="f32"):
    """See ``_train_fns``; compiled once per sizes, optimizer and
    precision."""
    return _train_fns(_frozen(sz), _frozen(opt), prec)


@functools.lru_cache(maxsize=None)
def _train_fns(sz, opt, prec):
    """Jitted parts of one AdamW step of the reference model:
    ``grad(params, tokens (b, S)) -> (loss, gradient)`` over the step's
    whole batch, since the balance loss counts each expert's picks over
    all of its tokens; ``clip(gradient)`` -> the gradient clipped by its
    global norm; and ``update(params, grads, m, v, count) -> (params, m,
    v)``: AdamW with weight decay on every leaf of two or more dimensions,
    as the program applies it to its stacked tree."""
    sz, opt = dict(sz), dict(opt)
    grad = jax.value_and_grad(lambda p, t: loss(p, t, sz, prec))

    def clip(g):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
        return jax.tree.map(lambda x: x * scale, g)

    def update(params, g, m, v, count):
        c = count.astype(jnp.float32)
        bc1, bc2 = 1 - opt["b1"] ** c, 1 - opt["b2"] ** c

        def upd(p, gi, mi, vi):
            mi = opt["b1"] * mi + (1 - opt["b1"]) * gi
            vi = opt["b2"] * vi + (1 - opt["b2"]) * gi * gi
            u = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
            if p.ndim >= 2:
                u = u + opt["weight_decay"] * p
            return p - opt["lr"] * u, mi, vi

        out = jax.tree.map(upd, params, g, m, v)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2)

    return (jax.jit(grad), jax.jit(clip, donate_argnums=(0,)),
            jax.jit(update, donate_argnums=(0, 1, 2, 3)))


def leaf_norms(tree):
    """Per-leaf float32 norms as one vector, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def train_readings(make_params0, batches, sz, opt, prec="f32",
                   devices=None):
    """Loss of each step, per-leaf norms of the first clipped gradient,
    and per-leaf norms of the parameters' change after ``len(batches)``
    AdamW steps, starting from ``make_params0()`` (the benchmark's
    weights, made again on the first device).  Every step runs on the
    first of ``devices`` (the balance loss needs the whole batch); the
    moments wait on the host while the gradient is computed."""
    dev = (devices or [None])[0]
    to32 = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32),
                                          t))
    p = to32(make_params0())
    grad, clip, update = make_train_fns(sz, opt, prec)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m = v = None
    losses, g1 = [], None
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            rows = jnp.asarray(tokens) if dev is None \
                else jax.device_put(tokens, dev)
            l, g = grad(p, rows)
            losses.append(float(l))
            g = clip(g)
            if i == 0:
                g1 = np.asarray(jax.jit(leaf_norms)(g))
                m, v = zeros(p), zeros(p)
            else:
                m, v = jax.device_put(m, dev), jax.device_put(v, dev)
            p, m, v = update(p, g, m, v, jnp.int32(i + 1))
            del g
            m, v = jax.device_get(m), jax.device_get(v)
        change = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(lambda x, y: x - y.astype(jnp.float32), a, b)))
        dp = np.asarray(change(p, make_params0()))
    return {"loss": losses, "grad1": g1, "change": dp}
