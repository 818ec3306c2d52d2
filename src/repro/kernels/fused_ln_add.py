"""Pallas TPU kernel for FAL's MLP-input fusion:  y = LN(x) + a1n.

This is the hot elementwise path FAL adds to every block (eq 2).  Fusing the
LayerNorm with the first-attention add performs one HBM read of x, one of
a1n, and one write of y — instead of materialising LN(x) to HBM first.
Row-tiled: grid over row blocks, the full feature dimension stays in VMEM
(d_model <= 8192 => <= 64 KB per row, fine).

Target: TPU.  Validated with ``interpret=True`` against
``repro.kernels.ref.ln_add_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ln_add_kernel(x_ref, a1_ref, scale_ref, bias_ref, o_ref, *, eps,
                   kind):
    x = x_ref[...].astype(jnp.float32)                    # (rows, d)
    if kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
        y = y * scale_ref[...].astype(jnp.float32) \
            + bias_ref[...].astype(jnp.float32)
    else:  # rmsnorm
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps)
        y = y * scale_ref[...].astype(jnp.float32)
    o_ref[...] = (y + a1_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def fused_ln_add(x, a1n, scale, bias=None, *, kind="rmsnorm", eps=1e-6,
                 block_rows=256, interpret=False):
    """x, a1n: (..., d) -> LN(x) + a1n, one pass."""
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    a2 = a1n.reshape(-1, d)
    rows = x2.shape[0]
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        a2 = jnp.pad(a2, ((0, pad), (0, 0)))
    n = x2.shape[0] // block_rows
    if bias is None:
        bias = jnp.zeros((d,), scale.dtype)

    kernel = functools.partial(_ln_add_kernel, eps=eps, kind=kind)
    out = pl.pallas_call(
        kernel,
        name="fused_ln_add",
        grid=(n,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, a2, scale, bias)
    return out[:rows].reshape(orig_shape)
