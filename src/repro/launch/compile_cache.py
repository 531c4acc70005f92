"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
nothing here touches it.  Otherwise the entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) keep the cache in one fixed
directory of the checkout, ``<repo>/.jax_cache`` (git-ignored), so a later
run of the same checkout compiles nothing it compiled before.  The path is
part of the cache key, so it never carries a temporary name, a pid or a
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_repo_compile_cache() -> str:
    """Turn the persistent cache on; return the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
