"""Plain reference of the FAL GPT-2 decoder, in float32 ``jax.numpy``.

It follows the paper's equations and the sizes of a configuration file
beside it, and imports nothing of the program.  Parameters come as the
benchmark makes them (``bench/weights.py``), in the program's tree layout:
``embed.emb`` (V, D), ``pos_emb`` (S, D), ``block0`` and the stacked
``blocks_dense`` (L-1, ...) with ``ln1``/``ln2``/``ln_a`` LayerNorms
(scale, bias), ``attn`` (wq, wk, wv: D x D, wo: D x D) and ``ffn``
(wi: D x F, wo: F x D), and ``final_norm``.

A block is ``x + MHA(ln1(x)) + MLP(m)`` where block 1 exports
``sig = ln_a(MHA_1)`` and every block's MLP reads ``m = ln2(x) + sig``
(eq. 2; block 1 reads its own signal).  The output head is tied to the
embedding.  GPT-2's bias terms in the attention projections and the MLP
are absent, as in the program; the LayerNorms keep theirs.

``prec`` selects the matmul precision: ``"f32"`` is the reference
(``highest``: float32 passes on the TPU); ``"fp8"`` rounds both operands
of every matmul, and in training the cotangent too, to float8 e4m3 with a
per-tensor scale, which is the control that one step below the program's
bfloat16 must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0


def sizes(conf: dict) -> dict:
    """The numbers the reference needs, read from a configuration file."""
    d = conf["n_embd"]
    return {"L": conf["n_layer"], "D": d, "H": conf["n_head"],
            "F": conf["n_inner"] or 4 * d, "V": conf["vocab_size"],
            "S": conf["n_positions"], "eps": conf["layer_norm_epsilon"]}


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


def _ln(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _attention(p, h, sz, prec):
    B, S, D = h.shape
    H = sz["H"]
    Dh = D // H
    q = _mm("bsd,de->bse", h, p["wq"], prec).reshape(B, S, H, Dh)
    k = _mm("bsd,de->bse", h, p["wk"], prec).reshape(B, S, H, Dh)
    v = _mm("bsd,de->bse", h, p["wv"], prec).reshape(B, S, H, Dh)
    s = _mm("bqhd,bkhd->bhqk", q, k, prec) / np.sqrt(Dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", w, v, prec).reshape(B, S, D)
    return _mm("bsd,de->bse", o, p["wo"], prec)


def _mlp(p, m, prec):
    h = jax.nn.gelu(_mm("bsd,df->bsf", m, p["wi"], prec), approximate=True)
    return _mm("bsf,fd->bsd", h, p["wo"], prec)


def hidden(params, tokens, sz, prec="f32"):
    """Final hidden states (B, S, D) of token ids (B, S)."""
    eps = sz["eps"]
    S = tokens.shape[1]
    x = (params["embed"]["emb"].astype(jnp.float32)[tokens]
         + params["pos_emb"].astype(jnp.float32)[:S][None])
    b0 = params["block0"]
    a1 = _attention(b0["attn"], _ln(b0["ln1"], x, eps), sz, prec)
    sig = _ln(b0["ln_a"], a1, eps)
    x = x + a1 + _mlp(b0["ffn"], _ln(b0["ln2"], x, eps) + sig, prec)

    @jax.checkpoint
    def block(x, bp):
        a = _attention(bp["attn"], _ln(bp["ln1"], x, eps), sz, prec)
        return x + a + _mlp(bp["ffn"], _ln(bp["ln2"], x, eps) + sig,
                            prec), None

    x, _ = jax.lax.scan(block, x, params["blocks_dense"])
    return _ln(params["final_norm"], x, eps)


def logits(params, tokens, sz, prec="f32"):
    """Logits (B, S, V) of token ids (B, S), head tied to the embedding."""
    return _mm("bsd,vd->bsv", hidden(params, tokens, sz, prec),
               params["embed"]["emb"], prec)


def loss(params, tokens, sz, prec="f32"):
    """Mean next-token cross-entropy over (B, S-1) positions."""
    lg = logits(params, tokens, sz, prec)[:, :-1]
    lse = jax.nn.logsumexp(lg, -1)
    tgt = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - tgt)


# --------------------------------------------------------------------- #
# training: three AdamW steps, gradients accumulated one row at a time
# --------------------------------------------------------------------- #
def _decays(path) -> bool:
    """AdamW as GPT-2 uses it: weight decay on the matmul weights and the
    embeddings, none on LayerNorm parameters."""
    names = [getattr(k, "key", None) for k in path]
    return not any(n in ("scale", "bias") for n in names)


def _frozen(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def make_train_fns(sz, opt: dict, prec="f32"):
    """See ``_train_fns``; compiled once per sizes, optimizer and
    precision."""
    return _train_fns(_frozen(sz), _frozen(opt), prec)


@functools.lru_cache(maxsize=None)
def _train_fns(sz, opt, prec):
    """Jitted parts of one AdamW step of the reference model:
    ``grad_sum(params, tokens (b, S)) -> (loss sum, gradient sum)`` over
    rows in a scan, so that one row's activations are live at a time;
    ``add`` of two gradient sums; ``clip(gradient sum, rows)`` -> the
    clipped mean gradient; and ``update(params, grads, m, v, count) ->
    (params, m, v)``.  Split so that rows can be spread over chips and
    the moments can wait on the host while gradients are computed."""
    sz, opt = dict(sz), dict(opt)
    grad_row = jax.value_and_grad(
        lambda p, row: loss(p, row[None], sz, prec))

    def grad_sum(params, tokens):
        def acc(carry, row):
            l_sum, g_sum = carry
            l, g = grad_row(params, row)
            return (l_sum + l, jax.tree.map(jnp.add, g_sum, g)), None

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)
        return jax.lax.scan(acc, (jnp.float32(0), zero), tokens)[0]

    def clip(g_sum, rows):
        g = jax.tree.map(lambda x: x / rows, g_sum)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
        return jax.tree.map(lambda x: x * scale, g)

    def update(params, g, m, v, count):
        c = count.astype(jnp.float32)
        bc1, bc2 = 1 - opt["b1"] ** c, 1 - opt["b2"] ** c

        def upd(path, p, gi, mi, vi):
            mi = opt["b1"] * mi + (1 - opt["b1"]) * gi
            vi = opt["b2"] * vi + (1 - opt["b2"]) * gi * gi
            u = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
            if _decays(path):
                u = u + opt["weight_decay"] * p
            return p - opt["lr"] * u, mi, vi

        out = jax.tree_util.tree_map_with_path(upd, params, g, m, v)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    return (jax.jit(grad_sum), add, jax.jit(clip, donate_argnums=(0,)),
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
    weights, made again on the first device).  Each step's rows are
    split over ``devices``, each holding a copy of the parameters; the
    gradient sums meet on the first device, which alone updates.  The
    moments wait on the host while gradients are computed, so that a chip
    holds the parameters, a gradient or two and one row's activations at
    a time."""
    devices = devices or [None]
    to32 = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32),
                                          t))
    p = to32(make_params0())
    grad_sum, add, clip, update = make_train_fns(sz, opt, prec)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m = v = None
    losses, g1 = [], None
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            copies = [p] + [jax.device_put(p, d) for d in devices[1:]]
            parts = [grad_sum(c, jnp.asarray(rows) if d is None
                              else jax.device_put(rows, d))
                     for c, d, rows in zip(copies, devices,
                                           np.array_split(tokens,
                                                          len(devices)))]
            del copies
            l_sum, g = parts[0]
            for l_k, g_k in parts[1:]:
                l_sum = l_sum + float(l_k)
                g = add(g, jax.device_put(g_k, devices[0]))
            del parts
            losses.append(float(l_sum) / len(tokens))
            g = clip(g, jnp.float32(len(tokens)))
            if i == 0:
                g1 = np.asarray(jax.jit(leaf_norms)(g))
                m, v = zeros(p), zeros(p)
            else:
                m, v = jax.device_put(m, devices[0]), jax.device_put(
                    v, devices[0])
            p, m, v = update(p, g, m, v, jnp.int32(i + 1))
            del g
            m, v = jax.device_get(m), jax.device_get(v)
        change = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(lambda x, y: x - y.astype(jnp.float32), a, b)))
        dp = np.asarray(change(p, make_params0()))
    return {"loss": losses, "grad1": g1, "change": dp}


# --------------------------------------------------------------------- #
# serving: the gap of each served token below the reference's best
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _logits_fn(sz, prec):
    return jax.jit(lambda p, t: logits(p, t, dict(sz), prec)[0])


def token_gaps(params, seqs, sz, prec="f32", pick=None):
    """For each (prompt, served tokens) in ``seqs``: at every served
    position, how far the served token's float32 reference logit lies
    below the reference's best logit there.  With ``pick="own"`` the
    token judged is instead the one this precision puts first (the
    control).  Returns one array of gaps per sequence."""
    f32 = jax.jit(lambda t: jax.tree.map(
        lambda x: x.astype(jnp.float32), t))(params)
    n_ctx = sz["S"]
    run_ref = _logits_fn(_frozen(sz), "f32")
    run_own = _logits_fn(_frozen(sz), prec)
    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, served in seqs:
            n0, n = len(prompt), len(served)
            toks = np.zeros((1, n_ctx), np.int32)      # one shape: one compile
            toks[0, :n0 + n] = np.concatenate([prompt, served])
            ref = np.asarray(run_ref(f32, toks))[n0 - 1:n0 - 1 + n]
            if pick == "own":
                own = np.asarray(run_own(f32, toks))[n0 - 1:n0 - 1 + n]
                tok = own.argmax(-1)
            else:
                tok = np.asarray(served)
            out.append(ref.max(-1) - ref[np.arange(n), tok])
    return out
