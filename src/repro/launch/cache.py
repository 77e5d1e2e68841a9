"""Where JAX keeps its persistent compile cache for this program's runs.

Compiling the build and search programs at a real size takes minutes, so
every entry point (`chip_smoke.py`, `launch/build_index.py`,
`launch/serve.py`, `benchmarks/run.py`) calls `enable_compile_cache()`
first.  Nothing calls it at import: a library user keeps whatever cache
policy their own process chose.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: src/repro/launch/cache.py -> parents[3]
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, wins: JAX reads it itself and
    this leaves it alone.  Otherwise the cache sits at one fixed path
    inside the checkout (listed in .gitignore).  The directory is part of
    every entry's key, so it never depends on a temp name, a pid or the
    time.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
