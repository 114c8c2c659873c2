"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call ``configure_compile_cache()`` once, before
their first compile. A ``JAX_COMPILATION_CACHE_DIR`` set from outside
wins: JAX reads it itself and nothing is set here. Otherwise the cache
goes to ``.jax_cache`` at the root of the checkout — a fixed path,
because the path is part of what a cache hit depends on. Tests never
call this, so they never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (git-ignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
