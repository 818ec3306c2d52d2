"""jit'd public wrappers for the Pallas kernels.

Each dispatcher runs one of three implementations of the same contract:

* ``fused-tpu`` — the compiled Pallas kernel (``use_pallas=True``; the
  default when JAX's default backend is a TPU);
* ``interpret`` — the same kernel under the Pallas interpreter
  (``interpret=True``; how the CPU tests run the kernels);
* ``cpu-fallback`` — the jnp oracle in ``kernels/ref.py`` or the jnp
  blockwise equivalent (``use_pallas=False``; the default elsewhere).

Model code selects one through ``ExecutionPlan.kernels``, which
``kernel_args`` turns into these keyword arguments.

Every dispatcher records the path it lowered per call site into the
default metrics registry (``repro.obs``) at trace time — a traced
program's path cannot change without a re-trace, so ``dispatch_paths()``
is the ground truth the benchmark JSONs stamp as ``dispatch_path`` (a
runtime measurement, not a bench-side guess).  ``kernel_dispatch_total``
therefore counts TRACES, not executed calls.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_ln_add as _fla
from repro.kernels import ref as _ref
from repro.obs import metrics as _metrics

FUSED = "fused-tpu"
INTERPRET = "interpret"
FALLBACK = "cpu-fallback"

#: ``ExecutionPlan.kernels`` values -> dispatcher keyword arguments
_KERNEL_ARGS = {
    "auto": {},
    "pallas": {"use_pallas": True},
    "interpret": {"interpret": True},
    "ref": {"use_pallas": False},
}

#: the fused dual-branch kernel tiles d_ff in lane-width (128) columns; a
#: narrower tile is refused by the TPU compiler
_LANE = 128

#: last path traced per dispatcher call site (survives registry resets:
#: a warmup reset must not un-measure an already-compiled program)
_DISPATCH_PATHS = {}


#: dotted suffix applied to the next recorded sites (``dispatch_site_suffix``)
_SITE_SUFFIX = ""


def kernel_args(kernels: str) -> dict:
    """Dispatcher keyword arguments for an ``ExecutionPlan.kernels`` value
    (``core.plan.KERNEL_PATHS``; ``ExecutionPlan.validate`` rejects
    others)."""
    return _KERNEL_ARGS[kernels]


def _path(use_pallas: bool, interpret: bool) -> str:
    if interpret:
        return INTERPRET
    return FUSED if use_pallas else FALLBACK


def _record_dispatch(site: str, path: str) -> str:
    if _SITE_SUFFIX:
        site = f"{site}.{_SITE_SUFFIX}"
    _DISPATCH_PATHS[site] = path
    _metrics.default_registry().counter(
        f"kernel_dispatch_total.{site}.{path}", unit="traces",
        site="kernels/ops.py").inc()
    return path


@contextlib.contextmanager
def dispatch_site_suffix(suffix: str):
    """Label dispatches traced inside the context with ``<site>.<suffix>``.

    Dispatch recording happens at TRACE time, so a caller that traces a
    sub-program under this context (e.g. the speculative-decode DRAFT
    early-exit forward inside the engine's one jitted tick) gets its kernel
    paths telemetered separately from the verify path's — same dispatcher,
    distinct ``dispatch_paths()`` rows (``paged_packed_attention`` vs
    ``paged_packed_attention.draft``)."""
    global _SITE_SUFFIX
    prev, _SITE_SUFFIX = _SITE_SUFFIX, suffix
    try:
        yield
    finally:
        _SITE_SUFFIX = prev


def _kv_variant(site: str, k_pages, k_scale) -> str:
    """Dotted site label for quantized-KV dispatches: a paged call with
    scale pools present traces as ``<site>.<storage>`` (e.g.
    ``paged_packed_attention.int8``) so runtime telemetry separates the
    quantized engine's kernel path from the unquantized one.  Scale-less
    calls keep the bare site name whatever the cache dtype."""
    if k_scale is None:
        return site
    name = jnp.dtype(k_pages.dtype).name
    if name.startswith("float8"):
        name = "fp8"
    return f"{site}.{name}"


def dispatch_paths() -> dict:
    """{call site: 'fused-tpu' | 'interpret' | 'cpu-fallback'} for every
    dispatcher traced so far in this process."""
    return dict(_DISPATCH_PATHS)


def reset_dispatch_paths():
    """Testing hook: forget recorded paths (jit caches survive, so only
    sites re-traced afterwards will reappear)."""
    _DISPATCH_PATHS.clear()


def _default_use_pallas():
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "use_pallas", "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    use_pallas=None, interpret=False):
    use_pallas = _default_use_pallas() if use_pallas is None else use_pallas
    _record_dispatch("flash_attention", _path(use_pallas, interpret))
    if use_pallas or interpret:
        return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                   block_k=block_k, interpret=interpret)
    from repro.models.attention import blockwise_attention
    return blockwise_attention(q, k, v, causal=causal, block_q=block_q)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                           k_scale=None, v_scale=None, use_pallas=None,
                           interpret=False):
    """Paged-KV decode attention: q (B,H,D) against (P,page,Hkv,D*) pools
    addressed through (B,T) block tables; optional (P,1,page) fp32
    ``k_scale``/``v_scale`` pools dequantize narrow-dtype pages at the
    VMEM load (fp32 softmax accumulate).  Pallas kernel on TPU;
    gather-based jnp oracle on CPU (identical numerics)."""
    use_pallas = _default_use_pallas() if use_pallas is None else use_pallas
    _record_dispatch(_kv_variant("paged_decode_attention", k_pages, k_scale),
                     _path(use_pallas, interpret))
    if use_pallas or interpret:
        from repro.kernels import paged_attention as _pa
        return _pa.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                          seq_lens, k_scale=k_scale,
                                          v_scale=v_scale,
                                          interpret=interpret)
    return _ref.paged_attention_ref(q, k_pages, v_pages, block_tables,
                                    seq_lens, k_scale=k_scale,
                                    v_scale=v_scale)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def paged_chunk_attention(q, k_pages, v_pages, block_tables, pos, n_valid, *,
                          k_scale=None, v_scale=None, use_pallas=None,
                          interpret=False):
    """Chunked paged attention (per-lane rectangular layout; the serving
    engine now packs tokens through ``paged_packed_attention``): q (B,C,H,D)
    chunks at per-lane positions ``pos`` (first ``n_valid`` rows of each
    lane valid, causal within the chunk) against (P,page,Hkv,D*) pools
    addressed through (B,T) block tables.  One dispatch serves lanes at ANY
    phase — prefilling lanes ride with n_valid up to C, decoding lanes with
    n_valid == 1; rows past a lane's ``n_valid`` are finite but meaningless
    and must not be read.  Pallas kernel on TPU; gather-based jnp oracle on
    CPU (identical numerics)."""
    use_pallas = _default_use_pallas() if use_pallas is None else use_pallas
    _record_dispatch(_kv_variant("paged_chunk_attention", k_pages, k_scale),
                     _path(use_pallas, interpret))
    if use_pallas or interpret:
        from repro.kernels import paged_attention as _pa
        return _pa.paged_chunk_attention(q, k_pages, v_pages, block_tables,
                                         pos, n_valid, k_scale=k_scale,
                                         v_scale=v_scale,
                                         interpret=interpret)
    return _ref.paged_chunk_attention_ref(q, k_pages, v_pages, block_tables,
                                          pos, n_valid, k_scale=k_scale,
                                          v_scale=v_scale)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def paged_packed_attention(q, k_pages, v_pages, block_tables, tok_slot,
                           tok_pos, *, k_scale=None, v_scale=None,
                           use_pallas=None, interpret=False):
    """Packed ragged paged attention (the token-packed serving kernel):
    q (T,H,D) — one flat token buffer where token t belongs to lane
    ``tok_slot[t]`` at logical position ``tok_pos[t]`` — against
    (P,page,Hkv,D*) pools addressed through per-SLOT (S,Tb) block tables.
    One dispatch serves lanes at ANY phase with FLOPs scaling in live
    tokens: a prefilling lane contributes up to ``chunk`` tokens, a
    decoding lane one — or, under self-speculative decoding, its whole
    n-token proposal: the VERIFY pass is this same kernel (a decode lane
    proposing n tokens is just a segment of length n at positions
    pos..pos+n-1; per-segment causality scores every proposal in the one
    dispatch, and K/V at later-rejected positions stay causally masked
    until overwritten when the position is re-fed).  Padding tokens carry
    tok_pos == -1 and emit exactly 0; callers must only read live rows.
    Pallas kernel on TPU; gather-based jnp oracle on CPU (identical
    numerics)."""
    use_pallas = _default_use_pallas() if use_pallas is None else use_pallas
    _record_dispatch(_kv_variant("paged_packed_attention", k_pages, k_scale),
                     _path(use_pallas, interpret))
    if use_pallas or interpret:
        from repro.kernels import paged_attention as _pa
        return _pa.paged_packed_attention(q, k_pages, v_pages, block_tables,
                                          tok_slot, tok_pos, k_scale=k_scale,
                                          v_scale=v_scale,
                                          interpret=interpret)
    return _ref.paged_packed_attention_ref(q, k_pages, v_pages, block_tables,
                                           tok_slot, tok_pos, k_scale=k_scale,
                                           v_scale=v_scale)


@functools.partial(jax.jit, static_argnames=("kind", "use_pallas",
                                             "interpret"))
def dual_branch_decode(q, k_pages, v_pages, block_tables, seq_lens, mlp_in,
                       ffn, *, kind="swiglu", use_pallas=None,
                       interpret=False):
    """Dual-branch decode tick: paged attention gather || dense FFN, issued
    as one dependency-free dispatch (the FAL MHA||MLP property at serving
    time).  q: (B, H, D) one query token per request; mlp_in: (B, 1, Dm)
    the block's MLP input (independent of this block's attention); ffn:
    dense-MLP params {"wi"[, "wg"], "wo"}.  Returns
    (attn (B, H, Dv), ffn_out (B, 1, Dm)).

    The fused Pallas kernel (``kernels.dual_branch``) runs both branches in
    ONE dispatch that overlaps the block-table page DMAs with the FFN
    matmuls.  It needs d_ff to split into Hkv*T column tiles of a multiple
    of 128 lanes; otherwise the branches are issued as two independent ops
    (XLA overlaps them), recorded under the site
    ``dual_branch_decode.two_op`` so the change of path is visible.  The
    CPU path runs exactly the ops of the sequential decode path — the
    gather-based ref oracle plus ``layers.mlp_apply`` — so dual-branch
    logits match sequential ones."""
    use_pallas = _default_use_pallas() if use_pallas is None else use_pallas
    from repro.models.layers import mlp_apply
    path = _path(use_pallas, interpret)
    n_tiles = k_pages.shape[2] * block_tables.shape[1]
    d_ff = ffn["wi"].shape[-1]
    if path != FALLBACK and d_ff % (n_tiles * _LANE) == 0:
        from repro.kernels import dual_branch as _db
        _record_dispatch("dual_branch_decode", path)
        attn, y = _db.fused_dual_branch_decode(
            q, k_pages, v_pages, block_tables, seq_lens, mlp_in, ffn,
            kind=kind, interpret=interpret)
        return attn, y
    _record_dispatch("dual_branch_decode.two_op", path)
    if path == FALLBACK:
        attn = _ref.paged_attention_ref(q, k_pages, v_pages, block_tables,
                                        seq_lens)
    else:
        from repro.kernels import paged_attention as _pa
        attn = _pa.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                          seq_lens, interpret=interpret)
    return attn, mlp_apply(ffn, mlp_in, kind)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def copy_pages(pool, src, dst, *, use_pallas=None, interpret=False):
    """COW page duplication: pool (P, page, ...) with the full rows at
    pages ``src`` (n,) copied over pages ``dst`` (n,) — the device memcpy
    behind ``BlockTable`` copy-on-write (a write into a prefix-shared page
    first lands the history on a private page).  Pallas in-place kernel on
    TPU (pool aliased into the output); scatter-based jnp oracle on CPU
    (identical bytes)."""
    use_pallas = _default_use_pallas() if use_pallas is None else use_pallas
    _record_dispatch("copy_pages", _path(use_pallas, interpret))
    if use_pallas or interpret:
        from repro.kernels import paged_attention as _pa
        return _pa.page_copy(pool, src, dst, interpret=interpret)
    return _ref.copy_pages_ref(pool, src, dst)


@functools.partial(jax.jit, static_argnames=("kind", "use_pallas",
                                             "interpret"))
def fused_ln_add(x, a1n, scale, bias=None, *, kind="rmsnorm",
                 use_pallas=None, interpret=False):
    use_pallas = _default_use_pallas() if use_pallas is None else use_pallas
    _record_dispatch("fused_ln_add", _path(use_pallas, interpret))
    if use_pallas or interpret:
        return _fla.fused_ln_add(x, a1n, scale, bias, kind=kind,
                                 interpret=interpret)
    return _ref.ln_add_ref(x, a1n, scale, bias, kind=kind)
