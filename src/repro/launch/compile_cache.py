"""JAX's persistent compilation cache for the serving entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
module sets nothing.  Otherwise the cache goes to one fixed, git-ignored
directory of the checkout: the path is part of the cache key, so it is never
built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent cache on (see module docstring); returns its
    directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
