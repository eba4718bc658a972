"""Persistent XLA compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``benchmarks/run.py``) call
:func:`use_compile_cache` before their first compile; library modules never
touch the cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
itself and nothing is set here.  Otherwise the cache goes to ``.jax_cache/``
at the repository root: a fixed path, because the path is part of what a
later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    that directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
