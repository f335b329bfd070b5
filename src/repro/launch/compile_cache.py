"""Persistent XLA compilation cache for the entry points.

A full-width step compiles for about a minute; the cache lets a second
run of the same program on the same chip skip that.  The entry points
(`chip_smoke.py`, `repro.launch.train`, `repro.launch.serve`,
`benchmarks/run.py`) call `enable_compile_cache()` once at start-up;
library code, imports and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The cache key includes the directory, so it is a fixed path: the
# checkout's `.jax_cache` (listed in .gitignore).
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache lives in
    `REPO_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
