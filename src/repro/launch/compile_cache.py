"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (the job service CLI, the benchmark runner, the examples and
``chip_smoke.py``) call :func:`enable_compile_cache` before their first
compile.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is changed here.  Otherwise the cache lives in ``.jax_cache`` at the
root of the checkout (git-ignored).  The path is fixed on purpose: it is
part of what a later process must find again, so it is never derived from
a temporary directory, a pid or the time.  Tests leave the cache off.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return the directory used."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
