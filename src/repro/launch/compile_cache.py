"""JAX's persistent compilation cache for the entry points.

A cold run compiles every program again (the full-width swarm round takes
tens of seconds on a TPU host).  ``enable()`` turns the persistent cache on:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it — the
  cache lives there and this module sets no other directory;
- otherwise it goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed on purpose: a directory named after
  a temp name, a pid or the time would be new on every run, and nothing
  cached in it would ever be found again.

Entry points call ``enable()`` under their ``__main__`` guard; library
code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
