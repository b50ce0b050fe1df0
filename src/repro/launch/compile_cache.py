"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/``) call :func:`enable_compile_cache` first thing; importing
this module does nothing. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
has already read it and this sets no other directory. Otherwise the cache
goes to one fixed directory inside the checkout (git-ignored): a fixed
path, because the path is part of what a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
