"""Where JAX keeps its persistent compilation cache.

A cold start on the chip compiles every program from scratch; the
persistent cache lets a later process of the same checkout load them
instead.  Its directory is part of what a later run must find, so it is
never a temporary name: either the one ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself) or ``.jax_cache`` at the root of
the checkout.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Call before the first compile of the process: JAX binds the cache to
    the directory it sees at that compile.  Every program is cached, not
    only those that took a second to compile: a serving process also
    compiles many small ones (admission, staging, stats) that each take
    less."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
