"""Persistent compilation cache location.

A cold process on the chip spends much of its set-up compiling. JAX's
persistent cache keeps compiled executables across processes, and the
cache directory is part of what it keys on, so it must not move
between runs. One rule, applied once at start-up by the entry points
(``chip_smoke.py``, ``benchmarks/run.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else
  is configured here;
* otherwise: ``<repo>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one fixed location
    and return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
