"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` before their first compile.
``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache lives
at a fixed path inside the checkout (``.jax_cache``, git-ignored), so
successive runs from one checkout find each other's compiled programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
