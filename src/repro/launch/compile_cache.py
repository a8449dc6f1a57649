"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/topo.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before they compile
anything; importing ``repro`` never does. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX has already read it and this code sets no other directory;
otherwise the cache lives at the fixed ``<repo>/.jax_cache`` — a stable path,
because the path is part of what a later run has to find again.
"""
from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
