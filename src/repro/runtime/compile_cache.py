"""Persistent XLA compilation cache for the entry points.

Each entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` once at start-up, so repeated runs on one
machine skip recompiling programs they already built.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here.
* Otherwise the cache lives at ``<repo root>/.jax_cache`` — a fixed path
  (never a temporary directory, a pid or a timestamp), since a cache that
  moves never hits.  The directory is listed in ``.gitignore``.

Tests never call this: a test run keeps JAX's default (no persistent
cache).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
