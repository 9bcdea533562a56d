"""Persistent XLA compile cache for the entry points.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``, ``repro.launch.train``) calls
:func:`use_compile_cache` before it compiles anything.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and this
sets no other directory.  Otherwise the cache goes to ``.jax_cache/`` at
the root of the checkout — a fixed path, never a temporary name, because a
cache directory that moves never hits.  Tests do not call this.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Optional[str]:
    """Point JAX's persistent compile cache at its directory and return
    the directory (None where JAX is not installed: the framework-free
    benchmarks compile nothing)."""
    try:
        import jax
    except ImportError:
        return None
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program, however fast it compiled: a cold process on the
    # chip would otherwise recompile each sub-second one
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
