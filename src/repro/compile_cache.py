"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points that compile real programs (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` before their first
compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and it is left alone. Otherwise the cache lives at ``<repo>/.jax_cache``:
a fixed path, because the path is part of what a later run must find.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = REPO_ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
