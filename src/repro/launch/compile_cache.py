"""JAX persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``examples/*.py``, the benchmark harness's
``build_env``) call :func:`enable_compile_cache` before their first
compile; importing ``repro`` sets nothing.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no other directory.  Otherwise the cache lives at a fixed path inside
the checkout: the path is part of what a later run looks the cache up by,
so it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]        # JAX took it from the environment
    path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
