"""Where JAX's persistent compilation cache lives for the launch entry points.

The cache's key includes its directory, so the directory must not move
between runs: either the one the environment names, or a fixed path inside
the checkout (git-ignored).  Called from ``main`` of an entry point, never
at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it as its
    ``jax_compilation_cache_dir`` option, and nothing is set here.
    Otherwise the cache goes to ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
