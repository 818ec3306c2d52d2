"""Placement of JAX's persistent compilation cache.

``enable_compile_cache()`` is the one place the program picks the cache
directory: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, and
otherwise ``.jax_cache/`` at the root of the checkout.  The default is a
fixed path on purpose: a per-run temp directory would never be hit again.
Entry points (``chip_smoke.py``, ``launch/train.py``) call it; library code
and tests do not.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
