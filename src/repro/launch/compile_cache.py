"""Where JAX keeps its persistent compilation cache.

A chip run compiles the round once per process; the cache lets the next
process (or the next call on the same machine) load the executable instead.
The cache key includes the directory, so the directory must not move: it is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself, and
nothing else is set here), and otherwise ``.jax_cache/`` at the root of
this checkout, resolved from this file's path.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
