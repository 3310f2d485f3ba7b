"""Plain reference of PageRank as the LDBC Graphalytics specification
states it: start from 1/n, damping ``alpha``, the rank of vertices without
out-edges spread evenly over all vertices, a fixed number of iterations.
Imports nothing of the program.

``control=True`` is the same iteration with the ranks and each edge's
contribution rounded to bfloat16 (the sums are summed as above).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def pagerank(src: np.ndarray, dst: np.ndarray, n: int, alpha: float,
             iters: int, control: bool = False) -> np.ndarray:
    deg = np.bincount(src, minlength=n).astype(np.float64)
    sink = deg == 0
    pr = np.full(n, 1.0 / n)
    for _ in range(iters):
        contrib = np.divide(pr, deg, out=np.zeros(n), where=~sink)[src]
        if control:
            contrib = contrib.astype(ml_dtypes.bfloat16).astype(np.float32)
        nxt = np.full(n, (1.0 - alpha) / n + alpha * pr[sink].sum() / n)
        nxt += alpha * np.bincount(dst, weights=contrib, minlength=n)
        pr = nxt
        if control:
            pr = pr.astype(ml_dtypes.bfloat16).astype(np.float64)
    return pr


def l1(got: np.ndarray, want: np.ndarray) -> float:
    """Sum of |got - want| (ranks sum to 1, so this is a share of the total
    rank); inf where `got` is not finite or has the wrong shape."""
    got = np.asarray(got, dtype=np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).sum())
