"""The benchmark's YCSB client: batches of GET/UPDATE operations drawn from
a traffic mix file, made from the seed before the measured window.

Keys follow YCSB's scrambled Zipfian: ranks are Zipf(``zipf_constant``) over
the record count, and one rank-to-key permutation drawn from the seed stays
fixed for the whole run, so the hot set does not move between batches.
Every batch holds exactly ``round(read_fraction * ops_per_batch)`` reads in
shuffled positions, so every seed sends batches of the same sizes. An
UPDATE is YCSB's read-modify-write of the whole record: ``x * mul + add``
with float32 operands in [0, 1).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Batch:
    keys: np.ndarray  # (n,) int64
    is_read: np.ndarray  # (n,) bool
    operand: np.ndarray  # (n, 2) float32: (mul, add)


class ScrambledZipf:
    def __init__(self, num_keys: int, theta: float, rng: np.random.Generator):
        cdf = np.cumsum(np.arange(1, num_keys + 1, dtype=np.float64) ** -theta)
        self.cdf = cdf / cdf[-1]
        self.perm = rng.permutation(num_keys).astype(np.int64)

    def ranks(self, n: int, rng: np.random.Generator) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(n), side="right")
        return np.minimum(r, self.cdf.size - 1)

    def draw(self, n: int, rng: np.random.Generator) -> np.ndarray:
        return self.perm[self.ranks(n, rng)]


class YCSBTraffic:
    """All batches a run can send: ``warm`` for set-up, ``pool`` for the
    window (sent in order, from the start again if the window outruns it)."""

    def __init__(self, mix: dict, num_keys: int, seed: int):
        self.rng = np.random.default_rng(seed)
        self.zipf = ScrambledZipf(num_keys, float(mix["zipf_constant"]),
                                  self.rng)
        self.n = int(mix["ops_per_batch"])
        self.n_read = int(round(float(mix["read_fraction"]) * self.n))
        self.warm = [self._batch() for _ in range(int(mix["warm_batches"]))]
        self.pool = [self._batch() for _ in range(int(mix["pool_batches"]))]

    def _batch(self) -> Batch:
        rng = self.rng
        keys = self.zipf.draw(self.n, rng)
        is_read = np.zeros(self.n, dtype=bool)
        is_read[rng.permutation(self.n)[:self.n_read]] = True
        operand = rng.random((self.n, 2), dtype=np.float32)
        return Batch(keys, is_read, operand)

    def check_rows(self, hot: int, zipf: int, uniform: int) -> np.ndarray:
        """Rows the reference follows at full width: the hottest keys, keys
        drawn as the traffic draws them, and keys drawn uniformly."""
        K = self.zipf.perm.size
        rng = self.rng
        return np.unique(np.concatenate([
            self.zipf.perm[:hot], self.zipf.draw(zipf, rng),
            rng.integers(0, K, uniform)]))
