"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the examples, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once at start-up; library code and the
tests never do.  The directory is part of each entry's key, so it must
not move between runs: ``$JAX_COMPILATION_CACHE_DIR`` when set,
otherwise the fixed ``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
