"""Where the entry points keep JAX's persistent compilation cache.

Called from the launchers' ``main`` (and ``chip_smoke.py``,
``benchmarks/chip/run.py``), never on import and never in tests: a
library user keeps control of their own JAX config.
"""
from __future__ import annotations

import os

import jax

#: fixed in-checkout default (``src/repro/launch`` -> checkout root); the
#: path is part of what a cache entry is found by, so it must not move
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places it — JAX reads that
    variable itself, so nothing else is set. Otherwise the cache lives at
    :data:`DEFAULT_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
