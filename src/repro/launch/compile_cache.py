"""JAX's persistent compilation cache for the command-line entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m benchmarks.run`` call :func:`enable_compile_cache` first
thing, so a second run of the same shapes loads its executables instead
of compiling them.  Importing ``repro`` never turns the cache on: the
test suite compiles from scratch.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: fixed in-checkout default (``<checkout>/.jax_cache``, git-ignored);
#: the directory is part of what a later run must find, so it never
#: depends on a temp name, a pid or the clock
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing here overrides it.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
