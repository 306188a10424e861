"""Persistent XLA compilation cache: one place decides where it lives.

JAX's persistent cache keys an entry on the compiled program and the
cache path, so a directory that moves between runs never hits.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets nothing; otherwise the cache goes to the fixed ``.jax_cache``
directory at the root of the checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/compile_cache.py -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Call before the first compile.  Never builds the path from a temporary
    directory, a process id or a time, so every run of one checkout
    shares the same cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
