"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``examples/serve.py``) call ``enable()`` once, before their first compile.
Library code and tests never call it, so importing the package changes
nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache lives in ``.jax_cache/`` at
the checkout root: a fixed path, because the path is part of what a later
run must match to find an earlier run's programs.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
