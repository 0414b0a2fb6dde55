"""Where JAX keeps its persistent compilation cache.

Only entry points call :func:`use_compile_cache` (``chip_smoke.py``,
``benchmarks/run.py``, the examples); importing the library never does, so
test processes and compiles against a described chip write no cache.

The cache key includes the directory, so the directory is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX reads
it itself), and otherwise ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout's root (this file is src/repro/compile_cache.py)
CHECKOUT_ROOT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
