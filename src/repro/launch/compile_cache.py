"""JAX's persistent compilation cache for the repository's entry points.

A cold process compiles every engine program again, tens of seconds per
grid shape on a TPU.  The cache serves those executables from disk on the
next run.  Its directory is part of each entry's key, so it must not move
between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads that
itself, and otherwise the cache lives at :data:`CACHE_DIR` inside the
checkout (listed in ``.gitignore``).

Call :func:`enable_compile_cache` once, from an entry point, before the
first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The fixed in-checkout cache directory used when the environment names
#: none.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory.  Every program is cached: the engine's kernels
    are many small compiles, each under JAX's default thresholds."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
