"""JAX's persistent compilation cache for the serving entry points.

A cold start on a chip compiles every program of the serving path; the
persistent cache lets later processes (the async front-end after a
synchronous run, a second engine of the same shapes, the next run) load
those executables instead. The cache directory is part of each entry's
key, so it is one fixed path: never a temporary directory, a pid or a
time.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (this file is <checkout>/src/repro/launch/...)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, where set, is the directory, and JAX
    reads it by itself; otherwise `<checkout>/.jax_cache`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
