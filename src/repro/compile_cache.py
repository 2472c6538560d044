"""Persistent XLA compilation cache for the entry points.

A cold process compiles every program it runs; on a TPU the served detector
program alone takes tens of seconds.  :func:`enable_compile_cache` is called
from each entry point's ``main()`` (never at import time, so importing a
module changes no global JAX state).
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache's fixed home inside the checkout (listed in .gitignore); a fixed
#: path matters because the cache directory is part of every entry's key
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it on its own
    and this function sets nothing.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
