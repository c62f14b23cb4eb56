"""JAX's persistent compilation cache for the entry points.

Called from the `main` of each entry point (training, serving, the
benchmark runner, `chip_smoke.py`), never at import, so a library user
keeps control of JAX's configuration.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
    sets nothing. Otherwise the cache lives at the fixed `<checkout>/.jax_cache`
    (the path is part of what a later run must find again)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
