"""Where JAX keeps its persistent compilation cache.

Called by the entry points (launch drivers, benchmark scripts,
``chip_smoke.py``), never at import: a library import must not change
global jax configuration.

A cold run of the HSS build is compile-heavy (the eager build compiles
every per-level shape, and adaptive builds recompile per shrunk shape), so
a second run in the same checkout should find the first run's programs.
The cache key includes the directory, so the directory is fixed: never a
temporary name, a pid or a timestamp.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (src/repro/launch/cache.py -> three parents up);
# listed in .gitignore.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at its one directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    this sets nothing.  Otherwise the cache goes to ``REPO_CACHE_DIR``.
    Returns the directory in use.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
