"""Public segment-combine op with backend selection.

The Phase-4 merge-able ⊗: `combine_add` dispatches to the Pallas kernel on
TPU while the shape fits the kernel's VMEM bound (`fits_pallas`), and to
the jnp scatter otherwise — on the device either way; `combine`
generalizes to the other set-associative merges from `core/mergeops.py`
(min / max / or) as jnp scatter reductions with the same
drop-out-of-range contract, so the jitted execution backend asks one op
for every merge. Rows whose segment id is >= num_segments are dropped —
the static-shape encoding of "writes nothing".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import routes
from .kernel import acc_bytes, segment_add
from .ref import segment_add_ref

# Largest f32 accumulator (V_pad × W_pad) the kernel may keep in VMEM. AOT
# compiles for a v5e (16 MiB default scoped VMEM, N=2^16 rows, blocks of
# 256) pass at 6 MiB for W_pad of 128, 256 and 512 (V = 12288, 6144, 3072)
# and fail at 7 MiB for W_pad of 256 and 512 (tests/test_tpu_compile.py
# compiles at this bound).
MAX_ACC_BYTES = 6 << 20


def fits_pallas(num_segments: int, width: int) -> bool:
    return acc_bytes(num_segments, width) <= MAX_ACC_BYTES


@functools.partial(jax.jit, static_argnames=("num_segments", "backend"))
def combine_add(values, seg, num_segments: int, *, backend: str = "auto"):
    route = routes.pick("segment_add", backend,
                        fits_pallas(num_segments, values.shape[1]))
    if route.startswith("ref"):
        return segment_add_ref(values, seg, num_segments)
    return segment_add(values, seg, num_segments,
                       interpret=(route == "interpret"))


@functools.partial(jax.jit, static_argnames=("num_segments", "op", "backend"))
def combine(values, seg, num_segments: int, *, op: str = "add",
            backend: str = "auto"):
    """Segment-⊗ for any set-associative merge: (N, W) values, (N,) seg ->
    (num_segments, W). Empty segments hold the merge identity."""
    if op == "add":
        return combine_add(values, seg, num_segments, backend=backend)
    out_shape = (num_segments,) + values.shape[1:]
    big = jnp.asarray(jnp.finfo(values.dtype).max, values.dtype)
    if op == "min":
        return jnp.full(out_shape, big, values.dtype).at[seg].min(
            values, mode="drop")
    if op == "max":
        return jnp.full(out_shape, -big, values.dtype).at[seg].max(
            values, mode="drop")
    if op == "or":
        return jnp.zeros(out_shape, values.dtype).at[seg].max(
            values, mode="drop")
    raise KeyError(f"no segment combine for merge op {op!r}")
