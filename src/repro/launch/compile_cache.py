"""Where JAX's persistent compilation cache lives.

A cache is found again only where the next run looks, so its directory is
never derived from a temp name, pid or time. Entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) call ``enable_compile_cache``
once, before their first compile; library code never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to the fixed
    ``DEFAULT_DIR`` inside the checkout."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
