"""JAX's persistent compilation cache, placed where the caller can find it.

Entry points (``launch/train.py``, ``benchmarks/run.py``, ``chip_smoke.py``)
call :func:`use_compile_cache` once before they compile anything, so a
second run on the same machine reuses compiled programs.  Nothing calls it
at import, so the test suite stays uncached.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, because the path is part of every cache
# key — a directory named after a pid, a time or a temporary never hits
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it as
    ``jax_compilation_cache_dir`` and nothing else is set.  Otherwise the
    cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
