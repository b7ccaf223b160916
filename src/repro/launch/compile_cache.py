"""JAX's persistent compilation cache for this repository's entry points.

A cold start of a full-width model compiles each step program for tens
of seconds; the cache lets the next process on the same machine load
them instead.  Entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) call ``use_compile_cache()``
once at start-up; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> Path | None:
    """The directory this process should set, or None when
    ``JAX_COMPILATION_CACHE_DIR`` is set — JAX then reads the variable
    itself and nothing is set in code.  Otherwise a fixed path inside the
    checkout: the path is part of the cache key, so it must not move."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return REPO_ROOT / ".jax_cache"


def use_compile_cache() -> Path | None:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    (when it names one); returns the directory set."""
    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
