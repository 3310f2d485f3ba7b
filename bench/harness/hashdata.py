"""Table values as a pure function of (seed, row, column).

Every float32 word of a YCSB table is ``value(seed, row * width + col)``:
a counter-based hash, so the device can make the whole table in bulk and
the reference can rebuild any rows or columns it needs without the rest.
Values are multiples of 2^-23 in [-1, 1): exact in float32 and float64.

The numpy and jax versions use the same uint32 arithmetic (products wrap
modulo 2^32 in both), so they agree bit for bit.
"""
from __future__ import annotations

import numpy as np

_M1, _M2 = 0x7FEB352D, 0x846CA68B  # lowbias32 (C. Wellons' hash prospector)


def seed_keys(seed: int) -> tuple[int, int]:
    """Two 32-bit keys from a seed of any size (splitmix64 finalizer)."""
    z = (int(seed) + 0x9E3779B97F4A7C15) % (1 << 64)
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) % (1 << 64)
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) % (1 << 64)
    z ^= z >> 31
    return z & 0xFFFFFFFF, z >> 32


def _mix(x, xp):
    x = x ^ (x >> 16)
    x = x * xp.uint32(_M1)
    x = x ^ (x >> 15)
    x = x * xp.uint32(_M2)
    return x ^ (x >> 16)


def _to_float(h, xp):
    # top 24 bits -> k * 2^-23 - 1, exact in float32
    return (h >> 8).astype(xp.float32) * xp.float32(2.0 ** -23) - xp.float32(1.0)


def values_np(seed: int, rows: np.ndarray, cols: np.ndarray,
              width: int) -> np.ndarray:
    """(len(rows), len(cols)) float32 table values."""
    k1, k2 = seed_keys(seed)
    idx = (np.asarray(rows, np.uint32)[:, None] * np.uint32(width)
           + np.asarray(cols, np.uint32)[None, :])
    with np.errstate(over="ignore"):
        h = _mix(_mix(idx ^ np.uint32(k1), np) ^ np.uint32(k2), np)
    return _to_float(h, np)


def device_rows_fn(seed: int, width: int, rows: int):
    """``f(first_row) -> (rows, width) float32``: a block of the table made
    on the default device. The seed's keys are arguments, not constants,
    so every seed and every block runs one compiled program."""
    import jax
    import jax.numpy as jnp

    keys = [jnp.uint32(k) for k in seed_keys(seed)]

    @jax.jit
    def block(first_row, k1, k2):
        r = first_row.astype(jnp.uint32) + jnp.arange(rows, dtype=jnp.uint32)
        idx = r[:, None] * jnp.uint32(width) + jnp.arange(width,
                                                          dtype=jnp.uint32)
        return _to_float(_mix(_mix(idx ^ k1, jnp) ^ k2, jnp), jnp)

    return lambda first_row: block(jnp.uint32(first_row), *keys)
