"""Merge-able write-back ⊗-combine (TD-Orch Phase 4 / DistEdgeMap
destination aggregation), Pallas TPU.

Accumulates per-destination sums for streamed (value, segment) tiles:
    out += onehot(seg_tile) @ values_tile
— MXU matmuls per tile (exact: `kernels/onehot.py`), no scatter. The
segment ids ride as one (1, N) lane-major row (a 1-D block would have to
match XLA's 1024-wide tiling of int32 vectors), so the (V, block_n) onehot
is a sublane iota compared against that row. The destination block (V × W)
stays resident in VMEM across the sequential grid, which bounds V × W
(`acc_bytes`); V is the per-shard vertex/row count (the graph partition or
the local expert/token slice), which is what TD-Orch's load balance bounds
to O(n/P).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..onehot import onehot_dot, split3

# rows per grid step: the (V, BLOCK_N) bf16 onehot is a VMEM temporary, and
# at 512 rows it pushes a 6 MiB accumulator past the scoped VMEM limit
BLOCK_N = 256


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def acc_bytes(num_segments: int, width: int) -> int:
    """Bytes of the f32 accumulator the kernel keeps in VMEM; the output
    block is as large, and the compiler's VMEM need grows with both."""
    return 4 * _rup(max(num_segments, 1), 128) * _rup(max(width, 1), 128)


def _seg_kernel(val_ref, seg_ref, o_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seg = seg_ref[...]  # (1, block_n)
    rows = jax.lax.broadcasted_iota(
        jnp.int32, (acc_ref.shape[0], seg.shape[1]), 0)
    onehot_dot(rows == seg, split3(val_ref[...].astype(jnp.float32)),
               ((1,), (0,)), acc=acc_ref)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def segment_add(values: jnp.ndarray, seg: jnp.ndarray, num_segments: int, *,
                block_n: int = BLOCK_N, interpret: bool = False
                ) -> jnp.ndarray:
    """values: (N, W); seg: (N,) int32 -> (num_segments, W). Out-of-range
    segment ids contribute nothing. `block_n` must be a multiple of 128."""
    N, W = values.shape
    block_n = min(block_n, _rup(max(N, 1), 128))
    N_pad = _rup(max(N, 1), block_n)
    V_pad = _rup(max(num_segments, 1), 128)
    W_pad = _rup(max(W, 1), 128)
    values = jnp.pad(values, ((0, N_pad - N), (0, W_pad - W)))
    # ids in [num_segments, V_pad) land on pad rows that are sliced off;
    # pad rows carry V_pad, which matches no row at all
    seg = jnp.pad(seg.astype(jnp.int32), (0, N_pad - N),
                  constant_values=V_pad)
    out = pl.pallas_call(
        _seg_kernel,
        grid=(N_pad // block_n,),
        in_specs=[pl.BlockSpec((block_n, W_pad), lambda i: (i, 0)),
                  pl.BlockSpec((1, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((V_pad, W_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((V_pad, W_pad), values.dtype),
        scratch_shapes=[pltpu.VMEM((V_pad, W_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(values, seg.reshape(1, N_pad))
    return out[:num_segments, :W]
