"""JAX's persistent compilation cache, kept where every run finds it again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
module sets nothing. Otherwise the cache goes to ``<repo>/.jax_cache``: a
fixed path, because the path is part of each entry's key, so a directory
that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
