"""Plain reference of a YCSB batch on a key-value table.

Batch semantics (the store's, stated in the configuration): every
operation reads the row as it stood before the batch; of the UPDATEs to one
key, the one earliest in the batch lands its ``x * mul + add``.

Rows are independent of one another and so are columns, so the reference
follows only what the check compares: a few columns of every row, and every
column of a few rows. It imports nothing of the program and rebuilds the
initial values from the seed. ``dtype`` is float64 for the reference and
bfloat16 for the control (every product and sum rounded to bfloat16).
"""
from __future__ import annotations

import numpy as np

from harness.hashdata import values_np


class Replay:
    def __init__(self, seed: int, num_keys: int, width: int,
                 cols: np.ndarray, rows: np.ndarray, dtype=np.float64):
        self.dtype = dtype
        self.cols = np.asarray(cols)
        self.rows = np.asarray(rows)
        self.by_col = values_np(seed, np.arange(num_keys), self.cols,
                                width).astype(dtype)
        self.by_row = values_np(seed, self.rows, np.arange(width),
                                width).astype(dtype)
        self.row_of = np.full(num_keys, -1, dtype=np.int64)
        self.row_of[self.rows] = np.arange(self.rows.size)

    def results(self, keys: np.ndarray, pos: np.ndarray):
        """What the batch reads: the followed columns of every operation,
        and the whole row of the operations at `pos` (keys among `rows`)."""
        return self.by_col[keys], self.by_row[self.row_of[keys[pos]]]

    def apply(self, keys: np.ndarray, is_read: np.ndarray,
              operand: np.ndarray) -> None:
        upd = np.flatnonzero(~is_read)
        uk, first = np.unique(keys[upd], return_index=True)
        win = upd[first]
        mul = operand[win, 0:1].astype(self.dtype)
        add = operand[win, 1:2].astype(self.dtype)
        self.by_col[uk] = self.by_col[uk] * mul + add
        hit = self.row_of[uk] >= 0
        r = self.row_of[uk[hit]]
        self.by_row[r] = self.by_row[r] * mul[hit] + add[hit]

    def table(self):
        return self.by_col, self.by_row
