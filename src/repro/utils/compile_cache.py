"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that compile real-size programs (``chip_smoke.py``,
``benchmarks/run.py``, ``benchmarks/workload_driver.py``) call
:func:`enable_compile_cache` once at start-up.  Tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

#: ``<repo>/.jax_cache`` — a fixed path, so a later process finds the
#: entries an earlier one wrote (listed in ``.gitignore``).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> Optional[str]:
    """The directory to set in code, or ``None`` when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX then reads it itself)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(DEFAULT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    import jax
    path = compile_cache_dir()
    if path is None:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", path)
    return path
