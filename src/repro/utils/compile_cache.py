"""Where JAX keeps its persistent compilation cache for this repo's scripts.

Called from entry-point scripts (``chip_smoke.py``, ``benchmarks/run.py``),
never at library import: a library that moved the cache would move it for
every program that imports it.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``
    (git ignores it): a fixed path, because the path is part of each entry's
    key.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
