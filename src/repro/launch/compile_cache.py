"""The one rule for JAX's persistent compilation cache.

A process that compiles the full-size model spends most of a cold start
compiling. Entry points call :func:`enable` before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing is
  set in code;
* otherwise: the cache lives at ``.jax_cache/`` in the checkout, one
  fixed directory that every run of this checkout shares.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Apply the rule; returns the directory the cache uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
