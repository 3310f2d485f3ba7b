"""Shared jitted JAX execution primitives (device side of the four phases).

One home for the jnp/Pallas machinery that used to be duplicated between the
SPMD realization (`core/spmd.py`) and ad-hoc call sites: the Phase-1
contention histogram (dispatching to `repro.kernels.histogram`), the Phase-2
routing permutation (stable group sort + capacity-bounded bucket routing),
the Phase-3 padded gather + lambda, and the Phase-4 merge-able
segment-combine (dispatching to `repro.kernels.segment_combine`, Pallas on
TPU, jnp scatter fallback otherwise). `core/backend.py`'s `JaxBackend`
drives the simulator's numeric pass through these; `core/spmd.py` wraps the
same primitives in shard_map for the production MoE path — the two no
longer carry parallel implementations of top-k hot-set election or group
sorting.

Everything here is jit-compiled with **static shapes**: callers pass
fixed-size arrays (padded where the logical size is dynamic — writer lists
are padded to power-of-two buckets so similar batches share one compiled
executable) and out-of-range indices (`mode="drop"`) realize the padding: a
row that should not participate scatters to an out-of-range segment and
vanishes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.histogram.ops import count_ids
from ..kernels.segment_combine.ops import combine as _kernel_combine
from ..kernels.stage_fused.ops import fused_stage as _fused_stage

# order sentinel for rows excluded from a "write" (first-writer-wins) combine
_ORDER_MAX = jnp.iinfo(jnp.int32).max

# what tracing raises for a stage lambda that cannot run under jit: numpy
# called on a tracer, Python control flow on a traced value. The backends
# route such a lambda to the numpy oracle; every other error raises.
UNTRACEABLE = (jax.errors.ConcretizationTypeError,
               jax.errors.TracerArrayConversionError,
               jax.errors.TracerIntegerConversionError)


# ---------------------------------------------------------------------------
# Phase 1: contention histogram (kernels.histogram dispatch)
# ---------------------------------------------------------------------------
def contention_counts(ids, num_bins: int, weights=None, *,
                      kernel_backend: str = "auto"):
    """Per-id demand histogram. Unweighted counts ride the Pallas histogram
    kernel (`repro.kernels.histogram.count_ids`, jnp fallback off-TPU);
    weighted counts (meta-task multiplicities) use the same op's weighted
    path. Returns int32 counts of length `num_bins`."""
    return count_ids(jnp.asarray(ids), num_bins, weights=weights,
                     backend=kernel_backend)


def detect_contention(item_ids, num_items: int,
                      axis_name: str | None = None, weights=None, *,
                      kernel_backend: str = "auto") -> jnp.ndarray:
    """Global reference count per data item (§3.1) — the one Phase-1
    primitive every realization shares: a per-shard histogram
    (`contention_counts`) plus, under SPMD, one `psum` over `axis_name` —
    on TPU an all-reduce *is* the balanced aggregation tree the paper
    builds by hand, so counts ride it directly. `core/spmd.py` (MoE
    dispatch) and `core/embedding.py` call this same function; pass
    ``axis_name=None`` for the single-device form."""
    counts = contention_counts(jnp.asarray(item_ids).reshape(-1), num_items,
                               weights=weights, kernel_backend=kernel_backend)
    if axis_name is not None:
        counts = lax.psum(counts, axis_name)
    return counts


def select_hot(counts: jnp.ndarray, num_hot: int, min_count: int = 1):
    """Top-`num_hot` items by demand, thresholded. Returns (hot_ids (H,),
    rank lookup (E,) with -1 = cold). Static H keeps shapes jit-stable —
    the SPMD analogue of the meta-task set's bounded size."""
    num_items = counts.shape[0]
    top_counts, hot_ids = lax.top_k(counts, num_hot)
    valid = top_counts >= min_count
    # invalid slots point at item 0 but are masked out of the lookup
    lookup = jnp.full((num_items,), -1, dtype=jnp.int32)
    ranks = jnp.arange(num_hot, dtype=jnp.int32)
    lookup = lookup.at[hot_ids].set(jnp.where(valid, ranks, -1), mode="drop")
    return hot_ids, lookup, valid


# ---------------------------------------------------------------------------
# Phase 2: routing permutations (stable sorts, capacity-bounded buckets)
# ---------------------------------------------------------------------------
def sort_by_group(ids: jnp.ndarray, num_groups: int):
    """Stable sort of assignments by group id; returns (order, group sizes).
    The routing permutation both the SPMD grouped compute and the jitted
    simulator backend use."""
    order = jnp.argsort(ids, stable=True)
    sizes = jnp.zeros(num_groups + 1, jnp.int32).at[ids].add(1)[:num_groups]
    return order, sizes


def inverse_permutation(order: jnp.ndarray) -> jnp.ndarray:
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))


@jax.jit
def stable_argsort(keys: jnp.ndarray) -> jnp.ndarray:
    """Stable argsort — bit-identical permutation to numpy's stable argsort
    (stability pins the order of equal keys, so the two agree exactly)."""
    return jnp.argsort(keys, stable=True)


# ---------------------------------------------------------------------------
# Phase 4: merge-able segment combine (kernels.segment_combine dispatch)
# ---------------------------------------------------------------------------
def _segment_combine(updates, seg, num_segments: int, merge_name: str, order):
    """⊗-combine `updates` rows per segment. seg == num_segments drops the
    row (the static-shape form of "this row writes nothing"); output rows
    beyond the live segment count are garbage the caller slices off.

    ``add``/``min``/``max``/``or`` dispatch to
    `repro.kernels.segment_combine.combine` (Pallas on TPU for ``add``).
    ``write`` realizes Definition 2 case (iv) exactly like the numpy
    oracle — lowest `order` in the segment wins, ties broken by row
    position — as two 1-D scatter-mins plus a gather (no wide scatter).
    """
    n = updates.shape[0]
    if merge_name in ("add", "min", "max", "or"):
        return _kernel_combine(updates, seg, num_segments, op=merge_name)
    if merge_name == "write":
        segc = jnp.clip(seg, 0, max(num_segments - 1, 0))
        live = seg < num_segments
        win_ord = jnp.full(num_segments, _ORDER_MAX, order.dtype).at[seg].min(
            order, mode="drop")
        tied = live & (order == win_ord[segc])
        rows = jnp.arange(n, dtype=jnp.int32)
        win_row = jnp.full(num_segments, n, jnp.int32).at[
            jnp.where(tied, seg, num_segments)].min(rows, mode="drop")
        # the winning row per segment, gathered (rows of empty segments are
        # garbage — they sit beyond the live segment count)
        return updates[jnp.clip(win_row, 0, max(n - 1, 0))]
    raise KeyError(f"merge op {merge_name!r} has no jax combine")


def _as_update_rows(upd, n: int, dtype):
    """Normalize a lambda's "update" output to (n, w) rows (the same
    atleast_2d/transpose coercion the numpy apply path performs)."""
    u = jnp.atleast_2d(jnp.asarray(upd, dtype=dtype))
    if u.shape[0] != n:
        u = u.T
    return u


# ---------------------------------------------------------------------------
# Phase 3 + 4 fused: gather → lambda → writer-compact ⊗-combine, one dispatch
# ---------------------------------------------------------------------------
def _finish_stage(out, values, w_idx, seg, order, *, merge_name: str,
                  combine: bool, want_update: bool, want_result: bool):
    """Shared tail of the fused stage: coerce the lambda output, ⊗-combine
    the writer rows (compacted through `w_idx` so combine cost scales with
    writers, not batch size), and drop what the host did not ask for — XLA
    dead-code-eliminates everything feeding an unreturned output (with
    `want_result=False` the per-task results are never even computed, so a
    StagePlan round pays no result transfer at all)."""
    out = dict(out) if out is not None else {}
    upd = out.get("update")
    combined = None
    if combine and upd is not None:
        u = _as_update_rows(upd, values.shape[0], values.dtype)
        uw = u[jnp.clip(w_idx, 0, u.shape[0] - 1)]
        combined = _segment_combine(uw, seg, w_idx.shape[0], merge_name, order)
    return {"result": out.get("result") if want_result else None,
            "update": upd if want_update else None,
            "combined": combined}


@functools.partial(jax.jit, static_argnames=(
    "f", "fwd_mask", "merge_name", "combine", "want_update", "want_result"))
def run_stage_flat(values, keys, contexts, w_idx, seg, order, *, f,
                   fwd_mask: bool, merge_name: str, combine: bool,
                   want_update: bool, want_result: bool = True):
    """Arity-≤1 stage numerics: gather each task's chunk (zeros where it
    reads nothing), run the lambda, ⊗-combine its writers' updates.
    `w_idx` (B,) lists writer task rows padded with n to a bucket size B;
    `seg[j]` is writer j's write-segment id (B = dropped padding); `order`
    its priority for "write" merges."""
    with jax.named_scope("phase3_gather_lambda"):
        has = keys >= 0
        gathered = jnp.where(has[:, None], values[jnp.clip(keys, 0)],
                             jnp.zeros((), values.dtype))
        out = f(contexts, gathered, has) if fwd_mask else f(contexts,
                                                            gathered)
    with jax.named_scope("phase4_combine"):
        return _finish_stage(out, gathered, w_idx, seg, order,
                             merge_name=merge_name, combine=combine,
                             want_update=want_update,
                             want_result=want_result)


@functools.partial(jax.jit, static_argnames=(
    "f", "fwd_mask", "merge_name", "combine", "want_update", "want_result"))
def run_stage_ragged(values, read_indices, row, col, mask, contexts, w_idx,
                     seg, order, *, f, fwd_mask: bool, merge_name: str,
                     combine: bool, want_update: bool, want_result: bool = True):
    """Ragged (multi-get) stage numerics: padded `(n, max_arity, w)` gather
    plus validity mask, then lambda + writer ⊗-combine as in
    `run_stage_flat`."""
    n, A = mask.shape
    w = values.shape[1]
    with jax.named_scope("phase3_gather_lambda"):
        gathered = jnp.zeros((n, A, w), values.dtype).at[row, col].set(
            values[read_indices], mode="drop")
        out = f(contexts, gathered, mask) if fwd_mask else f(contexts,
                                                             gathered)
    with jax.named_scope("phase4_combine"):
        return _finish_stage(out, gathered.reshape(n, A * w), w_idx, seg,
                             order, merge_name=merge_name, combine=combine,
                             want_update=want_update,
                             want_result=want_result)


def run_stage_fused(values, indptr, indices, pair_task, contexts, seg,
                    order, *, num_segments: int, read_op: str, finish,
                    merge_name: str, combine: bool, want_update: bool,
                    want_result: bool = True, kernel_backend: str = "auto"):
    """Ragged-native stage numerics for a fused-able lambda
    (`core/fusedlam.FusedStageLambda`): gather → `read_op` reduction →
    `finish` → writer ⊗-combine straight off the CSR pair list, one
    `kernels.stage_fused` dispatch (Pallas on TPU, jnp fallback elsewhere,
    `"interpret"` for the device-free conformance pin) — no
    `(n, max_arity, w)` padding, no materialized intermediates. The CSR
    geometry arrays are *host* arrays here (the kernel's tiling is computed
    from them); `seg` is per-task with `num_segments` meaning "writes
    nothing". Same output contract as `run_stage_flat`/`run_stage_ragged`.
    """
    upd, combined = _fused_stage(
        values, indptr, indices, pair_task, contexts, seg, order,
        num_segments=num_segments, read_op=read_op, finish=finish,
        merge_name=merge_name, combine=combine, backend=kernel_backend)
    upd = upd.astype(values.dtype)
    if combined is not None:
        combined = combined.astype(values.dtype)
    return {"result": upd if want_result else None,
            "update": upd if want_update else None,
            "combined": combined}


# donate the store buffer into the ⊙-apply where the platform supports
# in-place donation (accelerators); CPU XLA would only log donation warnings
_APPLY_DONATE = () if jax.default_backend() == "cpu" else (0,)


@functools.partial(jax.jit, static_argnames=("merge_name",),
                   donate_argnums=_APPLY_DONATE)
def apply_rows(values, uniq_padded, combined, *, merge_name: str):
    """⊙-apply combined updates to the device-resident store copy.
    `uniq_padded` is the sorted written-key list padded with ascending
    out-of-range keys (dropped) — sorted *and* unique, which XLA's scatter
    exploits; `combined` rows align with it."""
    kw = dict(mode="drop", unique_indices=True, indices_are_sorted=True)
    with jax.named_scope("phase4_apply"):
        if merge_name == "add":
            return values.at[uniq_padded].add(combined, **kw)
        if merge_name == "min":
            return values.at[uniq_padded].min(combined, **kw)
        if merge_name in ("max", "or"):
            return values.at[uniq_padded].max(combined, **kw)
        if merge_name == "write":
            return values.at[uniq_padded].set(combined, **kw)
    raise KeyError(f"merge op {merge_name!r} has no jax apply")


@functools.partial(jax.jit, static_argnames=("num_segments", "merge_name"))
def combine_dense(values, seg, *, num_segments: int, merge_name: str):
    """Dense segment combine over the full key range — the DistEdgeMap
    per-destination-vertex write-combine in one scatter."""
    return _segment_combine(values, seg, num_segments, merge_name,
                            jnp.zeros(values.shape[0], jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_segments",))
def sorted_segment_sum(values, order, seg, *, num_segments: int):
    """Segment-sum via the cached Phase-2 routing permutation: permute rows
    into segment-contiguous order, then one sorted segment reduction. The
    permutation is ingestion-time state for workloads that reuse one routing
    across stages (PageRank re-reduces the same edge set every round, like
    the paper's destination trees). `seg[i]` = segment of permuted row i,
    ascending. Each segment sums only its own rows, so the float32 error is
    relative to that segment — a difference of prefix sums over the whole
    edge list would carry an error relative to the total instead, which at
    2^18 vertices put PageRank 0.5% (L1) off the float64 oracle."""
    return jax.ops.segment_sum(values[order], seg, num_segments=num_segments,
                               indices_are_sorted=True)


# ---------------------------------------------------------------------------
# capacity-bounded bucket routing (SPMD push path; shared with spmd.py)
# ---------------------------------------------------------------------------
class Routing(NamedTuple):
    order: jnp.ndarray  # sort order over assignments
    dest: jnp.ndarray  # destination bucket per sorted assignment
    pos: jnp.ndarray  # position within bucket per sorted assignment
    keep: jnp.ndarray  # fits under capacity


def bucket_routing(dest: jnp.ndarray, num_buckets: int, capacity: int,
                   active: jnp.ndarray) -> Routing:
    """Stable-sort assignments by destination bucket and compute each one's
    slot; slots ≥ capacity are dropped (push-side overflow — rare once the
    hot items are pulled instead, which is the point of push-pull)."""
    big = jnp.asarray(num_buckets, dest.dtype)
    key = jnp.where(active, dest, big)  # inactive rows sort to the end
    order = jnp.argsort(key, stable=True)
    key_sorted = key[order]
    # position within each bucket = index − start(bucket)
    counts = jnp.zeros(num_buckets + 1, jnp.int32).at[key_sorted].add(1)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(dest.shape[0], dtype=jnp.int32) - starts[key_sorted]
    keep = (key_sorted < num_buckets) & (pos < capacity)
    return Routing(order=order, dest=key_sorted, pos=pos, keep=keep)


def scatter_to_buckets(rows: jnp.ndarray, routing: Routing, num_buckets: int,
                       capacity: int, fill=0) -> jnp.ndarray:
    """(A, d) rows -> (num_buckets, capacity, d) send buffer."""
    d_shape = rows.shape[1:]
    buf = jnp.full((num_buckets, capacity) + d_shape, fill, dtype=rows.dtype)
    src = rows[routing.order]
    return buf.at[routing.dest, routing.pos].set(
        jnp.where(routing.keep.reshape((-1,) + (1,) * len(d_shape)), src, fill),
        mode="drop",
    )


def gather_from_buckets(buf: jnp.ndarray, routing: Routing,
                        num_assign: int) -> jnp.ndarray:
    """Inverse of scatter_to_buckets: (B, cap, d) -> (A, d) in original
    assignment order (dropped slots read back as zeros)."""
    d_shape = buf.shape[2:]
    got = buf[routing.dest, routing.pos]
    got = jnp.where(routing.keep.reshape((-1,) + (1,) * len(d_shape)), got, 0)
    return got[inverse_permutation(routing.order)]
