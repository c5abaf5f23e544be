"""Persistent JAX compilation cache for the repository's entry points.

The scripts that drive the engine (``chip_smoke.py``,
``benchmarks/run.py``, ``benchmarks/serve_bench.py``) call
:func:`enable_compile_cache` before their first compile, so a second
process on the same machine reads compiled programs back instead of
compiling them again.  Importing the library turns nothing on: tests
compile as before.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``
(git-ignored), a fixed path: a directory that moved between runs would
never be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
