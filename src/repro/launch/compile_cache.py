"""Persistent XLA compilation cache for the entry points.

``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py`` call
:func:`enable` before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
here overrides it. Otherwise the cache goes to one fixed directory
inside the checkout (``<repo>/.jax_cache``, git-ignored): the path is
part of the cache key, so it never depends on a temp name, a pid or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its one directory
    and return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
