"""Persistent XLA compilation cache, placed from outside.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache and JAX reads it
itself; nothing here overrides it.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout root (listed in ``.gitignore``): the path
is part of the cache key, so it never holds a temporary name, a process
id or a time.  A second run of the same program on the same checkout then
loads its compiled programs instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
