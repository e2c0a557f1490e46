"""The comparisons that decide ``correct``: numbers of the timed path against
the plain reference, each reduced to one value held against its limit."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def worst_leaf_gap(program: Sequence[float], reference: Sequence[float],
                   keep: Optional[Sequence[bool]] = None) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's reference norm and the median
    leaf's (so a leaf whose norm is all but zero does not dominate)."""
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    keep = np.ones(len(r), bool) if keep is None else np.asarray(keep, bool)
    denom = np.maximum(r, np.median(r[keep]))
    return float(np.max(np.abs(p - r)[keep] / denom[keep]))


def moved_leaves(reference_grad: Sequence[float]) -> np.ndarray:
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's: the others move under Adam by round-off alone."""
    g = np.asarray(reference_grad, np.float64)
    return g > 1e-3 * np.median(g)


def relative_gap(program: Sequence[float], reference: Sequence[float]) -> float:
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))


def logit_gaps(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: how far the served token's logit lies below the best
    logit (0 where the served token is the best)."""
    logits = np.asarray(logits, np.float64)
    chosen = np.take_along_axis(logits, np.asarray(tokens)[..., None], -1)[..., 0]
    return logits.max(-1) - chosen
