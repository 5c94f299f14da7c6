"""Persistent JAX compilation cache for the repo's entry points.

``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` before their first
compile, so a second process on the same checkout reads compiled programs
back instead of compiling again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here.
* Otherwise the cache lives at ``<repo>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed because it is part of what a later
  process looks up: a temporary, per-process or per-run directory would
  never be hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
