"""Process-wide JAX set-up shared by the entry points.

``enable_compilation_cache`` places JAX's persistent compilation cache so
repeated runs (separate processes included) skip lowering and compilation.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory; otherwise the cache lives at one fixed path
inside the checkout (``DEFAULT_CACHE_DIR``).  The path is part of the cache
key, so it is never made from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Call before the first compile.  The activation thresholds (minimum entry
    size / minimum compile time) would skip small, fast compiles, so both are
    forced off and every executable is cached.  Raises ``OSError`` when the
    directory cannot be created or written: a cache that silently does
    nothing would make every run pay full compilation."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    if not os.access(path, os.W_OK):
        raise OSError(f"compilation cache directory {path!r} is not writable")
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
