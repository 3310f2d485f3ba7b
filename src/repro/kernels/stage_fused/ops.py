"""Public ragged fused-stage op with backend selection.

One call runs TD-Orch Phases 3+4 for a *fused-able* stage lambda — a
declared per-pair reduction (``read_op``) plus an optional elementwise
``finish`` epilogue (see `core/fusedlam.py`) — straight off the CSR pair
list: gather → reduce → finish → writer-segment ⊗-combine, no
`(n, max_arity, w)` padding anywhere.

Backends mirror the other kernel families: ``"pallas"`` is the fused TPU
kernel (`kernel.py`), ``"interpret"`` the same kernel interpreted on CPU
(the conformance suite's device-free pin), ``"ref"`` the jitted jnp
fallback (`ref.py`) used automatically off-TPU — and on TPU whenever the
value table or segment count would blow the kernel's VMEM budget.

Unlike the dense families this op is *not* top-level jitted: the tiling
geometry is computed host-side from the concrete CSR arrays (which callers
should bucket-pad — `core/backend.py` does — so the per-shape jit caches
underneath stay small).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import routes
from .kernel import _rup, fused_stage_pallas, output_width
from .ref import fused_stage_ref

FUSED_READ_OPS = ("add", "min", "max", "first")
FUSED_MERGES = ("add", "min", "max", "or", "write")

# Fast-memory bounds of the Pallas kernel on a v5e (16 MiB default scoped
# VMEM, 1 MiB SMEM), fitted to AOT compiles (tests/test_tpu_compile.py
# compiles at them). The value table rides VMEM single-buffered as three
# bfloat16 parts: it compiles at 24 MiB (K = 32768 at w = 32, 16384 at
# w = 250) and not at 48. The gather's bfloat16 onehot (K_pad × block_p)
# and two f32 blocks of the combine (S_pad × wo_pad) share the scoped VMEM:
# every compile with that sum at 12 MiB passed (K = 32768 with S = 4096;
# K = 16384 with S = 8192), every one at 14 MiB or more failed. The pair
# lists stream from HBM and have no bound. The per-task write segment and
# order plus the tile bounds ride scalar prefetch in SMEM: 2^16 tasks
# (0.6 MiB) compile, 2^17 (1.14 MiB) do not.
TABLE_BUDGET = 24 << 20
SCOPED_BUDGET = 12 << 20
SMEM_BUDGET = 768 << 10


def fits_pallas(num_keys: int, width: int, num_segments: int,
                num_tasks: int, w_out: int | None = None,
                block_p: int = 128) -> bool:
    """Whether the fused kernel's VMEM and SMEM hold this stage."""
    wo = width if w_out is None else w_out
    k_pad = _rup(num_keys, 16)
    table = 6 * k_pad * _rup(width, 128)
    scoped = (2 * k_pad * block_p
              + 8 * _rup(num_segments, 128) * _rup(wo, 128))
    n_pad = _rup(num_tasks + 1, 8)
    smem = 4 * (2 * n_pad + n_pad // 4 + _rup(num_segments, 128))
    return (table <= TABLE_BUDGET and scoped <= SCOPED_BUDGET
            and smem <= SMEM_BUDGET)


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "read_op", "finish", "merge_name", "combine"))
def _ref_jit(values, indptr, indices, pair_task, contexts, seg, order, *,
             num_segments, read_op, finish, merge_name, combine):
    return fused_stage_ref(values, indptr, indices, pair_task, contexts,
                           seg, order, num_segments=num_segments,
                           read_op=read_op, finish=finish,
                           merge_name=merge_name, combine=combine)


def fused_stage(values, indptr, indices, pair_task, contexts, seg, order, *,
                num_segments: int, read_op: str, finish=None,
                merge_name: str = "add", combine: bool = True,
                backend: str = "auto", block_t: int = 8,
                block_p: int = 128):
    """Fused ragged stage: ``(updates (n, w_out), combined
    (num_segments, w_out))`` (combined None when ``combine`` is False).

    `indptr`/`indices`/`pair_task`/`seg`/`order` are host arrays (the
    Pallas tiling is computed from them); `values`/`contexts` may be
    device-resident. A task whose ``seg == num_segments`` is dropped from
    the combine; rows of un-hit segments hold the merge identity.
    """
    if read_op not in FUSED_READ_OPS:
        raise KeyError(f"fused read op {read_op!r} not in {FUSED_READ_OPS}")
    if combine and merge_name not in FUSED_MERGES:
        raise KeyError(f"merge op {merge_name!r} has no fused combine")
    n = np.asarray(indptr).shape[0] - 1
    c = int(contexts.shape[1]) if contexts.ndim > 1 else 0
    w_out = output_width(finish, values.shape[1], c, block_t)
    route = routes.pick("stage_fused", backend, fits_pallas(
        values.shape[0], values.shape[1], num_segments, n, w_out, block_p))
    if route.startswith("ref"):
        return _ref_jit(jnp.asarray(values), jnp.asarray(indptr),
                        jnp.asarray(indices), jnp.asarray(pair_task),
                        jnp.asarray(contexts), jnp.asarray(seg),
                        jnp.asarray(order), num_segments=num_segments,
                        read_op=read_op, finish=finish,
                        merge_name=merge_name, combine=combine)
    return fused_stage_pallas(values, indptr, indices, pair_task, contexts,
                              seg, order, num_segments=num_segments,
                              read_op=read_op, finish=finish,
                              merge_name=merge_name, combine=combine,
                              w_out=w_out, block_t=block_t, block_p=block_p,
                              interpret=(route == "interpret"))
