"""Where the entry points keep JAX's persistent compilation cache.

Importing this module changes nothing; an entry point calls
``enable_compile_cache()`` from its ``main``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, git-ignored, inside the checkout: the path is part of the cache's
# key, so a directory that moved between runs would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, wins
    (JAX reads it itself and nothing here overrides it); otherwise the
    cache goes to ``REPO_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
