"""The one place the persistent XLA compilation cache is switched on.

Launchers (``train_forest``, ``serve_http``, ``serve_forest``, ``refresh``,
``ingest``) and ``chip_smoke.py`` call :func:`enable_compile_cache` at the
start of ``main``; importing a module never touches the cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets no other directory. Otherwise the cache lives at ``.jax_cache`` in the
checkout (git-ignored). The path is fixed — never built from a temp name, a
pid or the time — because it is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
