"""JAX's persistent compilation cache, set up by the entry points.

A cold start on a TPU compiles every step program (tens of seconds at
full width); the persistent cache lets a later process of the same
checkout load them instead. Only the entry points call
:func:`setup_compile_cache` — importing this module sets nothing.

A later process finds the cache only at the same path, so the path is
fixed: the ``JAX_COMPILATION_CACHE_DIR`` the environment names (JAX
reads it itself), or else ``<checkout>/.jax_cache``, which
``.gitignore`` lists.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
