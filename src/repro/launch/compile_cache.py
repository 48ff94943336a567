"""JAX's persistent compilation cache for the entry-point scripts.

A script calls ``enable_compile_cache()`` once, before its first compile.
Importing ``repro`` never turns the cache on: the tests treat warnings as
errors, and a cache entry written for one backend warns when read back on
another.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "enable_compile_cache"]

#: the variable JAX reads its cache directory from
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root (``src/repro/launch`` -> three levels up)
_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here. Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``: the directory is part of each entry's key,
    so it must not move between runs.

    Each entry's key also holds the program's op metadata (the engines'
    ``jax.named_scope`` scopes, which profiles read), with source paths
    taken relative to the checkout: without it, a program that differs
    from a cached one only in its scopes loads the cached executable and
    profiles show the old scopes."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{_CHECKOUT}{os.sep}"))
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
