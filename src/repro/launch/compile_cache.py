"""JAX's persistent compilation cache, at one fixed place.

Where JAX_COMPILATION_CACHE_DIR is set, JAX keeps its cache there and this
module sets nothing.  Otherwise the cache goes to `<repo>/.jax_cache` (in
.gitignore).  The path is part of every cache key, so it is never built
from a temporary name, a pid or the time: a run that moves it never hits.

Every entry point calls `enable()` before its first compile: chip_smoke.py,
repro.launch.train, repro.launch.serve, benchmarks/run.py and the
examples.
"""
from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn the cache on; returns its directory.

    With JAX not yet imported the directory is handed over through the
    environment (JAX reads it at import, and child processes inherit it),
    so calling this does not import JAX — a parent that spawns device
    workers must stay off JAX (benchmarks/run.py)."""
    path = os.environ.get(ENV)
    if path:
        return path
    if "jax" in sys.modules:
        import jax
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    else:
        os.environ[ENV] = REPO_CACHE
    return REPO_CACHE
