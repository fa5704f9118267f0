"""JAX's persistent compilation cache for the entry points.

A search plan compiles once per (backend, shape bucket, k, dispatch); on a
TPU that is seconds per program.  Entry points call ``enable()`` at start-up
(never at import) so a second process over the same code reuses those
programs.  ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and
JAX reads it itself; otherwise the cache lives at a fixed path inside the
checkout (``<repo>/.jax_cache``, ignored by git).  The path is part of each
entry's key, so it is never derived from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, not only those that took over a second: a plan
    # is many small stage programs plus the kernels.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
