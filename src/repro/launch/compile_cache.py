"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``)
call :func:`place_compile_cache` before their first compile.  The cache
directory is part of every entry's key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, nothing is set
  here;
* unset: the fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``),
  never a temp name, pid or timestamp.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def place_compile_cache() -> str:
    """Point the compilation cache at its one place; returns the path."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
