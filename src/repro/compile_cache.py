"""JAX's persistent compilation cache for the repo's entry points
(``chip_smoke.py``, ``benchmarks/engine_bench.py``, the examples).

The directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise ``.jax_cache`` at the root of the checkout: a fixed path, so
later runs of the same programs hit it (the path is part of the key).
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent cache on for every compile that takes at
    least a second; returns the directory used."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
