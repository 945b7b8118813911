"""Where JAX's persistent compilation cache lives for this checkout.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``)
call :func:`enable_compile_cache` once before their first compile; library
code never does, so importing a module changes no global JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Fixed directory inside the checkout (listed in ``.gitignore``). It never
#: depends on a temp name, pid or time: the path is part of what a cache
#: entry is found by, so a directory that moved between runs would never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` set in the environment stays in charge
    (JAX reads it itself); otherwise the cache goes to :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
