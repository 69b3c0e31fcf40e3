"""Where JAX keeps its persistent compilation cache.

Entry points — ``chip_smoke.py``, the examples and ``benchmarks/run.py``
— call :func:`use_compile_cache` once, before their first compile;
library code never does. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing here names another directory. Otherwise the
cache lives at ``<checkout>/.jax_cache``: a fixed path, so the next run
of the same checkout finds what this one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on for every compile, however short
    (the stage-1 kernels compile in well under JAX's default 1 s
    threshold), and return the directory it writes to."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
