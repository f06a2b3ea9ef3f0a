"""JAX's persistent compilation cache, kept at one fixed place.

A compiled program is found again only when the cache directory is the
same from run to run, so the directory never depends on a temp name, a pid
or the time.  ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads
it itself; nothing is set in code); otherwise the cache lives in the
checkout, under the gitignored ``.jax_cache/``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Call before the first compilation of the process: JAX fixes the cache
    when it first compiles.
    """
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


__all__ = ["ENV_CACHE_DIR", "DEFAULT_CACHE_DIR", "enable_compile_cache"]
