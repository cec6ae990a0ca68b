"""JAX's persistent compile cache, set up once for every entry point.

``python chip_smoke.py``, ``python -m benchmarks.run``, ``python -m
repro.service`` and every fleet worker call ``enable_compile_cache`` before
they compile anything, so processes that share a checkout also share
compiled programs.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

#: the checkout's own cache directory (listed in ``.gitignore``).  A fixed
#: path: the directory is part of what a cache hit needs.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    it itself and no other is set here.  Otherwise the cache lives at
    ``DEFAULT_DIR`` inside the checkout.  Every program is cached, however
    fast it compiled: the atom and segment programs take well under JAX's
    default one-second threshold, and a process fleet compiles them once
    per worker.

    A process held to the CPU (``JAX_PLATFORMS=cpu``) keeps no cache and
    gets ``None``: XLA:CPU logs a machine-feature mismatch for every entry
    it loads back, and its compiles are cheap.
    """
    if (jax.config.jax_platforms or "").split(",") == ["cpu"]:
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
