"""Where the entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself when it is imported. When
that variable is set, this module leaves the setting alone. When it is
not, the entry points (``chip_smoke.py``, ``benchmarks/*.py``,
``repro.launch.serve``, ``repro.launch.train``) call
:func:`init_compile_cache` before their first compile, which points the
cache at one fixed directory inside the checkout. The path is part of the
cache's key, so it never depends on a temporary name, a process id or the
time. Library code never turns the cache on: test runs compile without it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else :data:`CHECKOUT_CACHE_DIR`.
    Every compile is kept, however short: the sweep's kernels compile in
    under JAX's default one-second floor."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
