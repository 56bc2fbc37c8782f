"""JAX's persistent compilation cache for the entry points.

A compiled step program is keyed by its HLO and by the cache directory,
so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set
(JAX reads it itself and nothing is set here), else ``<repo>/.jax_cache``.
Never a temporary, pid- or time-based path, which would never hit.
"""
from __future__ import annotations

import os
import pathlib

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation of the process: JAX fixes the
    cache directory when it first compiles."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
