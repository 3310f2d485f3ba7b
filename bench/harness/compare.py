"""The one error measure every check uses, and the check record."""
from __future__ import annotations

import numpy as np


def max_err(got, want) -> float:
    """Largest |got - want| / max(1, |want|) over all entries; inf where
    `got` is not finite or the shapes differ."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    if not np.isfinite(got).all():
        return float("inf")
    return float((np.abs(got - want) / np.maximum(np.abs(want), 1.0)).max())


class Worst:
    """Running maximum of one compared number."""

    def __init__(self):
        self.value = 0.0

    def add(self, got, want) -> None:
        self.value = max(self.value, max_err(got, want))
