"""JAX's persistent compilation cache for the repository's entry points."""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (``src/repro/launch/`` is three levels below it)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, because a later process finds
    an entry only under the directory it was written to."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
