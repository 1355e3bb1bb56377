"""Persistent XLA compilation cache placement.

A serving process compiles its prefill and decode steps once per shape;
at full model width that is tens of seconds a process. JAX can keep the
compiled programs on disk. The cache path is part of the cache's key, so
it must not move between runs: it is never built from a temporary name,
a pid or the time.

Call :func:`enable_compile_cache` once at process start, before the first
compile. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this sets nothing else. Otherwise the cache goes to ``.jax_cache/`` at the
repository root (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
