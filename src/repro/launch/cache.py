"""Where this checkout keeps what it builds at run time.

``enable_compile_cache`` turns on JAX's persistent compilation cache for the
entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
before their first compile, so a second process on the same machine loads
its executables instead of compiling them again. Tests do not call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Root of the checkout (this file is ``src/repro/launch/cache.py``).
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

#: The compile cache when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path, because the path is part of the cache key.
DEFAULT_COMPILE_CACHE = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is left as it is (JAX reads it
    itself); otherwise the cache goes to `DEFAULT_COMPILE_CACHE`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
