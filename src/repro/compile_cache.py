"""JAX's persistent compilation cache for the repo's command-line programs.

``enable_compile_cache`` is called from the ``main`` of each program
(``chip_smoke.py``, ``examples/*``, ``benchmarks/run.py``), never at import
and never from library code the tests call.  The cache's location is part
of its key, so it lives at a fixed place: where ``JAX_COMPILATION_CACHE_DIR``
says, which JAX reads by itself, and otherwise ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
