"""Persistent XLA compilation cache for the entry points.

A full-depth model's prefill and decode programs take tens of seconds to
compile cold. Every entry point calls ``enable_compile_cache()`` first
thing in ``main``: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
uses that directory and nothing else is set; otherwise the cache goes to
the fixed path ``<checkout>/.jax_cache`` (the path is part of the cache
key, so it must not move between runs).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The directory the compilation cache lives in."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    unless the environment already does; returns the directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
