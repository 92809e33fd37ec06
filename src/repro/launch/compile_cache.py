"""JAX's persistent compilation cache for the entry points.

Compiling the training step for a TPU takes tens of seconds; the cache
lets the next process that builds the same program load it instead. That
process looks in the same directory only if its name does not change, so
the default is a fixed path inside the checkout (``.jax_cache``, listed
in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this leaves it alone. Otherwise the cache goes to ``DEFAULT_DIR``.
    Call it before the first compilation.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
