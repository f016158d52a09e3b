"""JAX's persistent compilation cache for the entry points.

Called first by ``chip_smoke.py``, ``repro.launch.serve`` and
``repro.launch.train``; no library module calls it. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed. Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed path,
because the path is part of the cache key and a moving directory never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
