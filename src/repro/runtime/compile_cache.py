"""Where JAX keeps its persistent compilation cache.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the entry points call ``configure()`` to keep
the cache at a fixed directory inside the checkout, ``<repo>/.jax_cache``
(git-ignored): the path is part of the cache key, so it must never be built
from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def configure() -> str:
    """Point JAX at the cache directory in force and return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["configure", "DEFAULT_DIR"]
