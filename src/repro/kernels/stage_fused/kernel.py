"""Ragged-native fused stage kernel (TD-Orch Phases 3+4), Pallas TPU.

One kernel walks the CSR (`read_indptr`/`read_indices`) pair list directly
— gather, per-task `read_op` reduction, `finish` epilogue, and
writer-segment ⊗-combine — with no `max_arity` padding and no intermediate
HBM round-trips. flash_attention-style tiling: the grid is
(task tiles × pair blocks) with the pair dim innermost sequential; each
task tile streams the aligned `block_p`-wide blocks that cover its own pair
range from HBM (per-tile bounds ride scalar prefetch and drive the block
index map, the moe_gemm idiom), masks the lanes outside the range, and
reduces into a VMEM accumulator. Pair keys and owners travel as (1, nnz)
lane-major rows and per-task scalars as (n, 1) columns, so no block is a
rank-1 vector. Gathers are onehot-matmuls against the VMEM-resident value
table (the histogram idiom — no scatter/gather primitives), so a skewed
batch pays for its *actual* pairs, not `n × max_arity`. Every matmul is
exact in f32 (`kernels/onehot.py`): the table arrives split into three
bfloat16 parts, and the per-task and per-segment sums split their operand
in the kernel.

The ⊗-combine accumulates across tiles in a VMEM scratch: ``add`` as a
(seg-onehot)ᵀ·updates MXU matmul; ``min``/``max``/``or`` as per-row
dynamic-slice reductions; ``write`` (Definition 2 case iv) keeps the
lowest-order / lowest-row winner per segment via a strict-compare scratch
of winning orders — tiles visit tasks in ascending row order, so a strict
`<` reproduces the oracle's tie-break exactly.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..onehot import onehot_dot, split3

# finite fill that survives float32 (the merge identities in core/mergeops.py
# are float64 ±FMAX, which overflow f32)
_BIG = float(np.finfo(np.float32).max) / 2
_ORDER_MAX = np.iinfo(np.int32).max

# combine-scratch init per merge op — matching the jnp fallback
# (`segment_combine.ops.combine`) on every *hit* segment; un-hit segments
# hold these identities (garbage the caller slices or drops by key)
_COMB_INIT = {"add": 0.0, "or": 0.0, "write": 0.0,
              "min": float(np.finfo(np.float32).max),
              "max": -float(np.finfo(np.float32).max)}


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _fused_kernel(tiles_ref, segp_ref, ordp_ref, seg_ref, starts_ref,
                  arity_ref, ctx_ref, values_ref, idx_ref, pt_ref,
                  upd_ref, comb_ref, red_ref, acc_ref, word_ref, *,
                  read_op: str, finish, merge_name: str, combine: bool,
                  num_segments: int, w: int, c: int, w_out: int,
                  block_t: int, block_p: int):
    t = pl.program_id(0)
    p = pl.program_id(1)
    n_p = pl.num_programs(1)
    ps = tiles_ref[2 * t]
    pe = tiles_ref[2 * t + 1]
    bt, bp = block_t, block_p
    s_pad = acc_ref.shape[0]

    @pl.when((t == 0) & (p == 0))
    def _init_combine():
        acc_ref[...] = jnp.full_like(acc_ref, _COMB_INIT[merge_name])
        if merge_name == "write":
            def _fill(i, carry):
                word_ref[i] = jnp.int32(_ORDER_MAX)
                return carry
            jax.lax.fori_loop(0, s_pad, _fill, 0)

    @pl.when(p == 0)
    def _init_reduce():
        fill = {"add": 0.0, "first": 0.0, "min": _BIG,
                "max": -_BIG}[read_op]
        red_ref[...] = jnp.full_like(red_ref, fill)

    # this step's aligned pair block (the index map fetched the same one,
    # clamped in range; a clamped block starts past `pe` and is skipped)
    start = (ps // bp + p) * bp

    @pl.when(start < pe)
    def _reduce_block():
        idx = idx_ref[...]  # (1, bp) requested chunk keys
        ptask = pt_ref[...]  # (1, bp) owning task rows
        gpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, bp), 1)
        live = (gpos >= ps) & (gpos < pe)
        # gather the block's pair values: onehotᵀ (K, bp) contracted with
        # the split values (3, K, w_pad) over K -> (bp, w_pad)
        krows = jax.lax.broadcasted_iota(
            jnp.int32, (values_ref.shape[1], bp), 0)
        g = onehot_dot((krows == idx) & live,
                       (values_ref[0], values_ref[1], values_ref[2]),
                       ((0,), (0,)))
        # local task membership: (bt, bp) onehot of this tile's rows
        trows = jax.lax.broadcasted_iota(jnp.int32, (bt, bp), 0)
        toh = ((ptask - t * bt) == trows) & live
        if read_op == "add":
            onehot_dot(toh, split3(g), ((1,), (0,)), acc=red_ref)
        elif read_op == "first":
            first = toh & (gpos == starts_ref[...])  # starts: (bt, 1)
            onehot_dot(first, split3(g), ((1,), (0,)), acc=red_ref)
        else:
            # per task row: mask the block's pairs down the sublanes
            # (membership transposed to (bp, bt)) and reduce them
            fill = jnp.asarray(_BIG if read_op == "min" else -_BIG,
                               jnp.float32)
            member = toh.astype(jnp.float32).T
            for r in range(bt):
                m = jnp.where(member[:, r:r + 1] > 0, g, fill)
                cur = red_ref[r:r + 1, :]
                red_ref[r:r + 1, :] = (
                    jnp.minimum(cur, m.min(axis=0, keepdims=True))
                    if read_op == "min"
                    else jnp.maximum(cur, m.max(axis=0, keepdims=True)))

    @pl.when(p == n_p - 1)
    def _finalize_tile():
        red = red_ref[...]
        if read_op in ("min", "max"):
            # arity-0 rows reduce to 0 (the oracle's zero-filled gather)
            red = jnp.where(arity_ref[...] > 0, red,
                            jnp.zeros((), jnp.float32))
            red_ref[...] = red
        if finish is None:
            upd_ref[...] = red  # w_out == w: same padded width
        else:
            upd_ref[...] = jnp.zeros_like(upd_ref)
            upd_ref[:, :w_out] = finish(ctx_ref[:, :c],
                                        red_ref[:, :w]).astype(jnp.float32)
        if not combine:
            return
        fin = upd_ref[...]  # (bt, wo_pad), zero past w_out
        if merge_name == "add":
            # (bt, s_pad) seg onehot; sᵀ·fin on the MXU
            scols = jax.lax.broadcasted_iota(jnp.int32, (bt, s_pad), 1)
            onehot_dot(scols == seg_ref[...], split3(fin), ((0,), (0,)),
                       acc=acc_ref)
            return
        base = t * bt
        for i in range(bt):  # ascending rows — order ties break low
            si = segp_ref[base + i]
            alive = si < num_segments
            sc = jnp.clip(si, 0, s_pad - 1)
            cur = acc_ref[pl.ds(sc, 1), :]
            row = fin[i:i + 1, :]
            if merge_name == "min":
                acc_ref[pl.ds(sc, 1), :] = jnp.where(
                    alive, jnp.minimum(cur, row), cur)
            elif merge_name in ("max", "or"):
                acc_ref[pl.ds(sc, 1), :] = jnp.where(
                    alive, jnp.maximum(cur, row), cur)
            else:  # "write": strictly-lower order wins
                oi = ordp_ref[base + i]
                take = alive & (oi < word_ref[sc])
                word_ref[sc] = jnp.where(take, oi, word_ref[sc])
                acc_ref[pl.ds(sc, 1), :] = jnp.where(take, row, cur)

    @pl.when((t == pl.num_programs(0) - 1) & (p == n_p - 1))
    def _emit_combined():
        comb_ref[...] = acc_ref[...]


class Geometry(NamedTuple):
    """Host-side tiling of one CSR batch: the padded arrays the kernel
    reads and the static grid sizes it is compiled for."""

    tiles: np.ndarray  # (2·nt,) int32 [pair start, pair end) per task tile
    segp: np.ndarray  # (n_pad,) int32 write segment per task
    ordp: np.ndarray  # (n_pad,) int32 write order per task
    starts: np.ndarray  # (n_pad, 1) int32 first pair position per task
    arity: np.ndarray  # (n_pad, 1) int32 pairs per task
    idx: np.ndarray  # (1, nnz_pad) int32 requested key per pair
    pair_task: np.ndarray  # (1, nnz_pad) int32 owning task per pair
    np_blocks: int  # pair blocks the widest task tile spans, pow2


def geometry(indptr, indices, pair_task, seg, order, *, num_segments: int,
             block_t: int = 8, block_p: int = 128) -> Geometry:
    """Tile a CSR batch (host numpy). Pad tasks own an empty pair range;
    pad pairs sit past every real tile's range and are never live."""
    indptr = np.asarray(indptr, dtype=np.int64)
    n = indptr.shape[0] - 1
    nnz = int(indptr[-1])
    n_pad = _rup(n + 1, block_t)  # ≥ 1 pad task, always
    nt = n_pad // block_t
    edges = np.concatenate([indptr, np.full(n_pad - n, nnz)])
    tiles = np.stack([edges[0:n_pad:block_t],
                      edges[block_t:n_pad + 1:block_t]], axis=1)
    live = tiles[:, 1] > tiles[:, 0]
    spans = (-(-tiles[:, 1] // block_p)) - tiles[:, 0] // block_p
    # a power of two, so batches of similar skew share one compiled grid;
    # blocks past a tile's range are skipped
    np_blocks = 1 << (int(spans[live].max(initial=1)) - 1).bit_length()
    nnz_pad = _rup(max(nnz, 1), block_p)
    idx = np.zeros((1, nnz_pad), dtype=np.int32)
    idx[0, :nnz] = indices
    pt = np.full((1, nnz_pad), n_pad - 1, dtype=np.int32)
    pt[0, :nnz] = pair_task
    segp = np.full(n_pad, num_segments, dtype=np.int32)
    segp[:n] = seg
    ordp = np.full(n_pad, _ORDER_MAX, dtype=np.int32)
    ordp[:n] = order
    return Geometry(
        tiles=tiles.reshape(-1).astype(np.int32), segp=segp, ordp=ordp,
        starts=edges[:n_pad, None].astype(np.int32),
        arity=np.diff(edges)[:, None].astype(np.int32),
        idx=idx, pair_task=pt, np_blocks=np_blocks)


@functools.partial(jax.jit, static_argnames=(
    "np_blocks", "read_op", "finish", "merge_name", "combine",
    "num_segments", "w_out", "block_t", "block_p", "interpret"))
def fused_call(tiles, segp, ordp, starts, arity, idx, pair_task, values,
               contexts, *, np_blocks: int, read_op: str, finish,
               merge_name: str, combine: bool, num_segments: int,
               w_out: int, block_t: int = 8, block_p: int = 128,
               interpret: bool = False):
    """The compiled kernel over one `Geometry`: returns padded
    ``(updates (n_pad, wo_pad), combined (s_pad, wo_pad))``."""
    n_pad = segp.shape[0]
    nt = n_pad // block_t
    nb = idx.shape[1] // block_p
    K, w = values.shape
    c = int(contexts.shape[1]) if contexts.ndim > 1 else 0
    k_pad = _rup(max(K, 1), 16)  # sublanes of a bfloat16 table tile
    w_pad = _rup(max(w, 1), 128)
    c_pad = _rup(max(c, 1), 128)
    wo_pad = _rup(max(w_out, 1), 128)
    s_pad = _rup(max(num_segments, 1), 128)  # lane dim of the seg onehot
    vals_p = jnp.stack(split3(jnp.zeros((k_pad, w_pad), jnp.float32).at[
        :K, :w].set(values.astype(jnp.float32))))
    ctx_p = jnp.zeros((n_pad, c_pad), jnp.float32)
    if c:
        ctx_p = ctx_p.at[:contexts.shape[0], :c].set(
            contexts.astype(jnp.float32))

    def pair_block(t, p, tl, s, o):
        return (0, jnp.minimum(tl[2 * t] // block_p + p, nb - 1))

    def task_rows(t, p, tl, s, o):
        return (t, 0)

    def whole(t, p, tl, s, o):
        return (0, 0)

    def table(t, p, tl, s, o):
        return (0, 0, 0)

    kern = functools.partial(
        _fused_kernel, read_op=read_op, finish=finish,
        merge_name=merge_name, combine=combine, num_segments=num_segments,
        w=w, c=c, w_out=w_out, block_t=block_t, block_p=block_p)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # tiles, seg, order ride SMEM
            grid=(nt, np_blocks),
            in_specs=[
                pl.BlockSpec((block_t, 1), task_rows),  # seg column
                pl.BlockSpec((block_t, 1), task_rows),  # starts
                pl.BlockSpec((block_t, 1), task_rows),  # arity
                pl.BlockSpec((block_t, c_pad), task_rows),
                pl.BlockSpec((3, k_pad, w_pad), table,
                             pipeline_mode=pl.Buffered(1)),
                pl.BlockSpec((1, block_p), pair_block),
                pl.BlockSpec((1, block_p), pair_block),
            ],
            out_specs=[
                pl.BlockSpec((block_t, wo_pad), task_rows),
                pl.BlockSpec((s_pad, wo_pad), whole),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_t, w_pad), jnp.float32),
                pltpu.VMEM((s_pad, wo_pad), jnp.float32),
                pltpu.SMEM((s_pad,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, wo_pad), jnp.float32),
            jax.ShapeDtypeStruct((s_pad, wo_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tiles, segp, ordp, segp[:, None], starts, arity, ctx_p, vals_p, idx,
      pair_task)


def output_width(finish, w: int, c: int, block_t: int = 8) -> int:
    """Width of the stage output: `w`, or what `finish` maps it to."""
    if finish is None:
        return w
    return int(jax.eval_shape(
        finish, jax.ShapeDtypeStruct((block_t, c), jnp.float32),
        jax.ShapeDtypeStruct((block_t, w), jnp.float32)).shape[1])


def fused_stage_pallas(values, indptr, indices, pair_task, contexts, seg,
                       order, *, num_segments: int, read_op: str,
                       finish=None, merge_name: str = "add",
                       combine: bool = True, w_out: int | None = None,
                       block_t: int = 8, block_p: int = 128,
                       interpret: bool = False):
    """Host wrapper: numpy CSR geometry in, `(updates (n, w_out),
    combined (num_segments, w_out))` out. `indptr`/`indices`/`pair_task`/
    `seg`/`order` must be host arrays (the tiling is computed from them);
    `values`/`contexts` may live on device."""
    n = np.asarray(indptr).shape[0] - 1
    c = int(contexts.shape[1]) if contexts.ndim > 1 else 0
    if w_out is None:
        w_out = output_width(finish, values.shape[1], c, block_t)
    geo = geometry(indptr, indices, pair_task, seg, order,
                   num_segments=num_segments, block_t=block_t,
                   block_p=block_p)
    upd, comb = fused_call(
        *(jnp.asarray(a) for a in geo[:-1]), jnp.asarray(values),
        jnp.asarray(contexts), np_blocks=geo.np_blocks, read_op=read_op,
        finish=finish, merge_name=merge_name, combine=combine,
        num_segments=num_segments, w_out=w_out, block_t=block_t,
        block_p=block_p, interpret=interpret)
    return upd[:n, :w_out], (comb[:num_segments, :w_out] if combine
                             else None)
