"""Persistent XLA compilation cache for the entry points that run on a chip.

A cold 28-layer serving step takes minutes to compile; the cache lets the
next process with the same programs skip that.  JAX keys cache entries by
directory, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, so nothing is set in
code), else ``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
