"""Where JAX's persistent compilation cache lives.

A cold run on a chip compiles every segment program; the persistent cache
lets later processes skip that.  Its directory is part of the cache's key,
so it must never move: no temporary name, process id or time in the path.
Entry points call :func:`enable_compile_cache` before their first compile;
importing this module does nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and no directory is set in code; otherwise the cache lives at
    ``<checkout>/.jax_cache``.  Every program is cached, however quickly
    it compiled: a model's many small segment programs add up."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
