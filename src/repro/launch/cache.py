"""Where JAX keeps its persistent compilation cache.

Launchers call :func:`use_compile_cache` once, before their first
compile.  Importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: one
#: fixed path inside the checkout (gitignored).  The path is part of
#: what a cached entry is found by, so it never names a temp dir, a pid
#: or a time.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turns the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
