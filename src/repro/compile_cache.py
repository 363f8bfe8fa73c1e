"""Where JAX keeps its persistent compilation cache for this checkout."""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

# <checkout>/.jax_cache (gitignored). Fixed on purpose: the directory is
# part of what a later run has to find again.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    JAX_COMPILATION_CACHE_DIR, when set, wins: JAX reads it itself and
    nothing is set here. Otherwise the cache goes to CACHE_DIR. Called by
    process entry points (chip_smoke.py, benchmarks/run.py) before their
    first compile — never at import and never from tests."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
