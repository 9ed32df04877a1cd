"""JAX's persistent compilation cache, placed where a later run finds it.

A cache entry is keyed on the program and on the compiler, not on the
process, so a run that finds the directory of an earlier run skips its
compiles. Entry points call :func:`enable_compile_cache` once, before they
compile anything; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# A fixed path inside the checkout (listed in .gitignore): a directory
# whose name changed from run to run would never be found again.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here. Otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
