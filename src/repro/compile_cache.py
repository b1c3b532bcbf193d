"""JAX's persistent compilation cache for the program's entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache directory; JAX
reads the variable itself and nothing here overrides it.  Otherwise the
cache lives at a fixed path inside the checkout (``<checkout>/.jax_cache``,
git-ignored): the path is part of the cache key, so a directory that
moves between runs would never hit.

A function, not an import side effect: ``import repro`` stays free of
JAX, so launchers that set ``XLA_FLAGS`` first keep working.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
