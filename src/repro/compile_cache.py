"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the
``repro.launch`` drivers) calls :func:`enable_compile_cache` once at
start; nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path, because a cache entry is found
# again only under the directory it was written to
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory.  Otherwise the cache lives in
    ``<checkout>/.jax_cache``.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
