"""Persistent compilation cache shared by the launchers and chip_smoke.py.

A cold step program of a 24-layer model takes minutes to compile; with
the cache, every later process of the same checkout reads it back. The
cache directory is part of every entry's key, so it is a fixed path.
"""
from __future__ import annotations

import os

import jax

#: used when JAX_COMPILATION_CACHE_DIR is unset; git-ignored
CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile and return
    its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and no other directory is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
