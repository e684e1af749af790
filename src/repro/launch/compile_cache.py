"""JAX's persistent compilation cache, placed from outside or at one path.

A run on a fresh machine compiles everything; a later run that can read
the same cache directory skips those compiles.  The directory is part of
what makes an entry findable, so it must not move between runs: no temp
dir, pid or timestamp in it.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The checkout's own cache directory (listed in ``.gitignore``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to ``DEFAULT_DIR``.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
