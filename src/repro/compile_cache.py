"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` from ``main()`` (never at
import): where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this sets nothing; otherwise the cache goes to ``<checkout>/.jax_cache``.
The path is fixed because it is part of the cache key: a directory named
after a temp dir, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
