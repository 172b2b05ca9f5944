"""Persistent XLA compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call ``enable_compile_cache()`` once at startup; no library module calls it
as it is imported.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path, because the path is part of what
makes a later run find the entries, so it is never built from a temporary
name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
