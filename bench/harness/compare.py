"""The numbers that decide ``correct``, each from a program and a reference
reading of the same work."""
from __future__ import annotations

import numpy as np


def leaf_norms(before, after) -> list:
    """L2 norm of each leaf's change, as flat lists of float64 leaves."""
    return [float(np.linalg.norm(np.asarray(a, np.float64)
                                 - np.asarray(b, np.float64)))
            for b, a in zip(before, after)]


def norm_gap(prog: list, ref: list) -> float:
    """Worst leaf's gap between the program's and the reference's norm,
    against the reference's norm of that leaf or the median leaf's,
    whichever is larger.  Leaves the reference leaves all but unmoved
    (under a thousandth of the median leaf) are left out."""
    ref = np.asarray(ref, np.float64)
    prog = np.asarray(prog, np.float64)
    med = float(np.median(ref))
    moved = ref >= 1e-3 * med
    den = np.maximum(ref, med)
    return float(np.max(np.abs(prog - ref)[moved] / den[moved]))


def loss_gap(prog: list, ref: list) -> float:
    """Largest relative gap between two loss histories."""
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.max(np.abs(p - r) / np.abs(r)))


def forecast_gap(prog: np.ndarray, ref: np.ndarray, lo: np.ndarray,
                 hi: np.ndarray) -> float:
    """Widest gap of a served forecast from the reference's, in units of
    that consumer's min-max range (the model's normalised space)."""
    if not np.all(np.isfinite(prog)):
        return float("inf")
    scale = np.maximum(hi - lo, 1e-9).reshape(-1, 1)
    return float(np.max(np.abs(prog.astype(np.float64) - ref) / scale))
