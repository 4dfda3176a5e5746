"""JAX's persistent compile cache at a path that can be placed from outside.

A cold process compiles every program it runs; on a TPU host that can be
most of a short run. `enable()` turns the persistent cache on:

- if `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it as its
  cache directory, and nothing else is configured;
- otherwise the cache goes to `<checkout>/.jax_cache` — a fixed path, as
  the path is part of what makes later runs hit (`.gitignore` lists it).

Call it before the first compile.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
