"""JAX's persistent compilation cache, shared by every phase of a run.

A cold run compiles the generator's prefill and decode programs and every
retrieve kernel shape from nothing; with the cache on, a later run (or a
later phase of the same process) reads them back.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX takes the cache from it and
nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``: a
fixed path, because the path is part of the cache key and a directory that
moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
