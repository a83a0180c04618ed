"""JAX's persistent compilation cache, set up by the entry points.

A cold start on a TPU compiles every step program (tens of seconds at
full width); the persistent cache lets a later process of the same
checkout load them instead. Only the entry points call
:func:`setup_compile_cache` — importing this module sets nothing.

A later process finds the cache only at the same path, so the path is
fixed: the ``JAX_COMPILATION_CACHE_DIR`` the environment names (JAX
reads it itself), or else ``<checkout>/.jax_cache``, which
``.gitignore`` lists.

A cached executable keeps the op metadata it was compiled with, and a
profile names each op by it (the layer scopes of ``core/scopes.py``).
JAX leaves metadata out of the cache key by default, so a program whose
scopes changed would load an entry compiled from other source and be
profiled under its names. The key therefore includes the metadata, with
the checkout's own path cut from its file names, so that the same
source still finds its entries wherever it is checked out.
"""
from __future__ import annotations

import os
import pathlib
import re

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
