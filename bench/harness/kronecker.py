"""Graph500 Kronecker edge generator (Graph500 specification, section 3),
vectorized in numpy, with the clean-up of the LDBC Graphalytics graph500-*
datasets: self-loops and duplicate edges removed, every undirected edge
kept as two directed edges, vertices without an edge dropped and the rest
numbered 0..n-1 in the permuted label order.
"""
from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, rng: np.random.Generator):
    """(src, dst) of the raw edge list, vertex labels permuted."""
    n = 1 << scale
    m = edge_factor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    perm = rng.permutation(n).astype(np.int64)
    return perm[src], perm[dst]


def graphalytics_graph(scale: int, edge_factor: int, a: float, b: float,
                       c: float, seed: int):
    """(n, src, dst): symmetric, simple, no isolated vertices."""
    rng = np.random.default_rng(seed)
    s, d = kronecker_edges(scale, edge_factor, a, b, c, rng)
    keep = s != d
    s, d = s[keep], d[keep]
    n_raw = 1 << scale
    pairs = np.unique(np.minimum(s, d) * np.int64(n_raw) + np.maximum(s, d))
    lo, hi = pairs // n_raw, pairs % n_raw
    used = np.zeros(n_raw, dtype=bool)
    used[lo] = True
    used[hi] = True
    label = np.cumsum(used, dtype=np.int64) - 1
    lo, hi = label[lo], label[hi]
    return int(used.sum()), np.concatenate([lo, hi]), np.concatenate([hi, lo])
