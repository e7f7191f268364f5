"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory: JAX reads
it itself and nothing here overrides it.  Otherwise the cache lives at
a fixed path inside the checkout (``.jax_cache/`` at the repository
root, ignored by git).  The path is part of each entry's key, so it
must never move between runs: no temporary name, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place the persistent compilation cache; call before the first
    compile.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
