"""Persistent XLA compilation cache for the command-line entry points.

Drivers (``launch/serve.py``, ``launch/train.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call ``enable_compile_cache()`` before their first
compile, so a second run of the same program loads its executables from
disk instead of compiling them again.  Importing this module changes
nothing: tests and library users keep JAX's defaults.

The cache key includes the directory, so the directory is fixed:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads that variable
itself; no other directory is configured in code), and otherwise
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
