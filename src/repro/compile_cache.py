"""JAX persistent compile cache placement, shared by every entry point.

``enable()`` is called by the launchers' ``main``s and by ``chip_smoke.py``,
never at package import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing here overrides it.  Otherwise the cache goes
to ``<checkout>/.jax_cache``: a path derived from where the package lives,
so it is the same in every process and every run (the path is part of the
cache key; a directory that moves never hits).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` — this file lives at ``<checkout>/src/repro/``
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compile cache at its directory and return
    that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
