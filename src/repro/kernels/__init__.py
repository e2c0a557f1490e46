"""Pallas kernels for the hot spots the paper optimizes.  Each package has
``kernel.py`` (the Pallas TPU kernel), ``ops.py`` (entry points) and
``ref.py`` (the plain jnp oracle).

Where a kernel runs is decided here and nowhere else: the engine takes the
Pallas path by default only on a TPU backend, and interpret mode (the
Pallas interpreter) is chosen only on the CPU backend — never on a TPU.
"""
from __future__ import annotations

from typing import Optional

import jax


def use_kernels(flag: Optional[bool] = None) -> bool:
    """``flag`` if given, else True exactly on a TPU backend."""
    return jax.default_backend() == "tpu" if flag is None else flag


def interpret_mode(flag: Optional[bool] = None) -> bool:
    """``flag`` if given, else True exactly on the CPU backend."""
    return jax.default_backend() == "cpu" if flag is None else flag
