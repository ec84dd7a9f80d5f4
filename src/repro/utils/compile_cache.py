"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` first thing in ``main``
(never at import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and the cache lives there; nothing else is set.
Otherwise the cache goes to ``.jax_cache`` at the root of the checkout:
a fixed path, because the path is part of what a later run looks up.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
