"""Where the program's entry points keep JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, the ``benchmarks/`` scripts) call
:func:`enable_compile_cache` once, before their first compile — never at
import time, so importing the library changes no JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Fixed in-checkout cache directory (listed in ``.gitignore``).  The cache
#: key includes the directory, so it must not move between runs.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache lives in the fixed
    ``.jax_cache/`` at the repository root.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
