"""JAX's persistent compilation cache, kept where the next run finds it.

The cache key includes the directory, so a temp, pid or time path never
hits twice. `enable_compile_cache` honours ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads that variable itself, so no other directory is
set in code) and otherwise uses the fixed ``.jax_cache/`` at the root of
the checkout."""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for every compile of this
    process, however short, and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
