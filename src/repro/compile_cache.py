"""JAX's persistent compilation cache, placed from outside the code.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no path. Otherwise the cache lives at a fixed path inside the
checkout (``.jax_cache/``, git-ignored), so the next run finds it again:
a directory named after a pid, the time or a temporary name never would.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR_NAME = ".jax_cache"


def enable_compile_cache(checkout: str | os.PathLike) -> str:
    """Turn the persistent cache on for every compile, however short, and
    return the directory it writes to."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(pathlib.Path(checkout).resolve() / CACHE_DIR_NAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
