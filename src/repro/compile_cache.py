"""Persistent XLA compilation cache for the programs that run on a chip.

Compiling the routed insert/query steps at deployment sizes takes tens of
seconds per program, and every new process starts cold.  Call
``enable_compile_cache()`` before the first compile: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing else
is configured here; otherwise the cache lives at a fixed directory inside
the checkout (``.jax_cache/``, git-ignored) -- a fixed path, because the
path is part of what a later run must find again.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
