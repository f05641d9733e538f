"""JAX's persistent compilation cache, placed from outside.

Called from the ``main`` of each launcher (never at import): with
``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that directory and
nothing is changed here; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``.  The path is part of the cache key, so it must
not carry a temp name, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
