"""Where the entry points keep JAX's persistent compilation cache.

A cold start of the Llama3-8B-width server compiles several step variants;
keeping them on disk lets the next run of the same checkout skip that.  The
cache's key includes its path, so the path is fixed: never a temporary
name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is used as it is: JAX reads
    it itself, and nothing is changed here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Call this from an entry point's ``main``,
    never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
