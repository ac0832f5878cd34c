"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`use_compile_cache` before their first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives in ``<repo>/.jax_cache``:
a path that is the same on every run, so later runs find what earlier
ones compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the fixed fallback location (listed in .gitignore)
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
