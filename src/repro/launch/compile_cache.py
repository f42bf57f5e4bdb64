"""Persistent XLA compile cache for the entry points (serve, benchmarks,
chip_smoke.py).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
cache lives there; nothing else is set in code. Otherwise the cache goes
to ``<repo>/.jax_cache`` (git-ignored): a fixed path, because the path is
part of the cache key, so a directory that moves never hits. XLA_FLAGS
and LIBTPU_INIT_ARGS are left alone.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it.
    Call before the first compile."""
    import jax

    path = os.environ.get(ENV) or str(REPO_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
