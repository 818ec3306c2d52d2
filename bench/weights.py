"""The benchmark's weights and keys, made from ``--seed``.

Weights follow GPT-2's published initialisation: every matrix and both
embeddings are normal with standard deviation 0.02, the two residual
projections (``attn.wo``, ``ffn.wo``) 0.02 / sqrt(2 * n_layer); LayerNorm
scales are one and biases zero.  They fill the program's parameter tree,
whose layout and dtypes the program reports through ``jax.eval_shape``,
in one jitted call on the device.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A JAX key from a seed of any size (JAX alone keeps 32 bits of it)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def _std(path, n_layer: int):
    names = [getattr(k, "key", None) for k in path]
    if names[-1] == "scale":
        return None, 1.0
    if names[-1] == "bias":
        return None, 0.0
    if names[-1] == "wo":
        return 0.02 / math.sqrt(2 * n_layer), None
    return 0.02, None


def builder(shapes, n_layer: int, dtype=None):
    """``key -> params`` over the tree of ``ShapeDtypeStruct`` ``shapes``,
    for the caller to jit with the shardings it serves them in.  ``dtype``
    (the configuration's parameter type) overrides the dtype of every
    floating leaf."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = []
        for i, (path, s) in enumerate(flat):
            std, const = _std(path, n_layer)
            dt = dtype if dtype and jnp.issubdtype(s.dtype, jnp.floating) \
                else s.dtype
            if std is None:
                leaves.append(jnp.full(s.shape, const, dt))
            else:
                leaves.append((jax.random.normal(
                    jax.random.fold_in(key, i), s.shape, jnp.float32)
                    * std).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make
