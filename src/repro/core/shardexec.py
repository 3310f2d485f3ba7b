"""Mesh-sharded SPMD stage execution — the simulator's machines made real.

Until now every backend executed all P "machines" of a stage as one
single-device program: the cost model (`core/cost.py`) *charged* max-over-
machines work and h-relation volume, but nothing validated that the numeric
execution could actually be laid out that way. This module is that layout:
each shard of a `jax.shard_map` device mesh IS one machine — it materializes
only the `DataStore` chunks it homes (plus the session's `ReplicaSet`
entries), holds only the tasks the cost model placed on it (`exec_site`),
and runs the four phases locally with collective exchanges in between:

  Phase 1 (contention detection): each shard counts its active (task,
    requested-key) pairs by the key's owner shard, and one `psum` of those
    P bins gives every owner the demand on its chunks.
  Phase 2 (co-location): each pair sends a request to the key's owner
    shard via a bucketed power-of-two ragged `all_to_all` (the pow2 padding
    from the plan scope, so drifting batch sizes share compiled
    executables); owners reply with the chunk rows, a second `all_to_all`
    brings them home. Pairs whose chunk is in the shard's replica slab
    never touch the wire — they read the local copy.
  Phase 3: the stage lambda runs on each shard over its local gathered
    view — exactly the `jaxexec.run_stage_*` numerics, per shard.
  Phase 4: write-backs ⊗-combine *locally* per written key, the combined
    rows ride one more `all_to_all` to the owner shards, each owner
    ⊗-combines what it received per slab row and scatters the result into
    its slab with the merge's ⊙ (in place: the stage donates the slab), and
    written chunks that are replicated write-through their post-apply rows
    to every holder (a masked `psum` — the broadcast tree the hardware
    provides).

Every key-to-shard map the phases need (each pair's owner, slab row and
replica slot, each writer's owner and slab row) is looked up on the host
from `DataStore.shard_layout()` and handed to the program per shard, so no
operand, intermediate or transfer of a warm stage scales with the table:
only the resident slab does.

The contract that keeps this big change safe (`core/backend.py`
`SpmdBackend`): every cost-model input is still produced host-side by the
same code as the numpy oracle, so per-phase words/rounds are **bit-
identical** across backends, while the sharded values match the
single-device jax backend within float tolerance
(`tests/test_spmd_backend.py`, `tests/test_conformance.py`).

Everything here is static-shape jitted: per-shard task/pair counts pad to
power-of-two buckets, inactive slots carry sentinel keys that `mode="drop"`
scatters erase, and the compiled program is cached per
(lambda, shape-signature, merge) in the owning backend.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from . import execution
# per-shard task/pair counts pad with the plan scope's pow2 bucketing rule
# (one shared definition, so the two can never disagree on bucket shapes)
from .backend import _bucket_rows as _bucket
from .datastore import stable_bucket_slots
from .spans import span
from .jaxexec import (UNTRACEABLE, _as_update_rows, _segment_combine,
                      bucket_routing, gather_from_buckets,
                      scatter_to_buckets)

AXIS = "shards"
_IMAX = np.int32(np.iinfo(np.int32).max)


class ShardStageError(RuntimeError):
    """The stage lambda could not be traced (`jaxexec.UNTRACEABLE`) — the
    one fallback-eligible failure. Compile and runtime errors of the stage
    program and host-side placement/layout errors are deliberately NOT
    wrapped: those are bugs, and silently degrading to an unsharded run
    would invalidate every per-machine claim."""


# ---------------------------------------------------------------------------
# the device mesh (machines == shards)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=32)
def get_mesh(P: int) -> Mesh:
    """One 1-D mesh of the first `P` local devices: shard m IS machine m.

    Raises `RuntimeError` when the process has fewer devices than the store
    has machines — a silently-degraded "sharded" run on too few devices
    would invalidate every per-machine claim, so the failure is loud and
    names the CPU recipe.
    """
    devs = jax.devices()
    if P > len(devs):
        raise RuntimeError(
            f"backend='jax_spmd' needs one device per machine: the store "
            f"has P={P} machines but this process sees only "
            f"{len(devs)} device(s). On CPU, relaunch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={P} (set it "
            "before jax initializes), or shrink the store's machine count.")
    return Mesh(np.array(devs[:P]), (AXIS,))


def _a2a(x):
    """The bucketed ragged all-to-all: (P, cap, ...) send buffer -> same
    shape where row p holds what shard p sent to this shard."""
    return lax.all_to_all(x, AXIS, 0, 0)


# ---------------------------------------------------------------------------
# per-stage measured shard statistics
# ---------------------------------------------------------------------------
class ShardStageStats(NamedTuple):
    """What the sharded execution *measured* (per shard), as opposed to what
    the cost model charged: `tasks` per shard (== the cost model's Phase-3
    work placement), fetch/combine rows actually moved by the all-to-alls,
    replica-local reads, and the psum'd Phase-1 demand routed to each
    shard's owned chunks."""

    tasks: np.ndarray  # (P,) tasks executed on each shard
    pairs: np.ndarray  # (P,) active (task, key) pairs resident per shard
    fetch_sent: np.ndarray  # (P,) value requests sent into the a2a
    fetch_recv: np.ndarray  # (P,) requests received (owner-side demand)
    replica_local: np.ndarray  # (P,) pairs served from the replica slab
    writers: np.ndarray  # (P,) writing tasks per shard
    combine_sent: np.ndarray  # (P,) combined rows sent to owners
    combine_recv: np.ndarray  # (P,) combined rows received by owners
    owned_demand: np.ndarray  # (P,) global Phase-1 demand on owned chunks

    def work_ratio(self) -> float:
        """Measured max/mean task placement over shards (Definition 1)."""
        mean = float(self.tasks.mean()) if self.tasks.size else 0.0
        return float(self.tasks.max(initial=0.0) / max(mean, 1e-12))


# ---------------------------------------------------------------------------
# device residency (slabs per shard + replicated hot rows)
# ---------------------------------------------------------------------------
UPLOAD_ROWS = 1 << 16  # slab rows of every shard staged per upload block
TILE = (8, 128)  # sublanes x lanes of a TPU tile of 32-bit words


def slab_shape(rows: int, w: int) -> tuple:
    """(rows, words) of a shard's device slab: the layout's slab rows and
    the record's words rounded up to a TPU tile. A TPU keeps a (1, rows,
    words) shard row-major only when both fit its tiles; otherwise it lays
    the shard out with one-row tiles or with the words major, and every
    stage would relayout the whole slab, twice, to reach its rows (the
    tiles pad a row to the lanes either way). Padding rows and lanes are
    zeros nobody reads."""
    return tuple(-(-n // t) * t for n, t in zip((rows, w), TILE))


class SlabView:
    """The resident slabs read as the (P, slab_rows, w) array they hold.
    Indexing (integers, slices, integer or boolean arrays, one Ellipsis)
    keeps to that extent, never the padding rows or lanes of the device
    slabs (`slab_shape`), and reads only what it asks for, without a copy
    of the table on the device; `np.asarray` fetches the whole of it. Every
    other attribute (sharding, addressable_shards, devices, nbytes, ...) is
    the padded device array's: its shards hold (1,) + `slab_shape` rows."""

    ndim = 3

    def __init__(self, slabs, rows: int, w: int):
        self.slabs, self.rows, self.w = slabs, rows, w

    @property
    def shape(self) -> tuple:
        return (self.slabs.shape[0], self.rows, self.w)

    def __getattr__(self, name):
        if name == "slabs":
            raise AttributeError(name)
        return getattr(self.slabs, name)

    def __getitem__(self, idx):
        idx = idx if isinstance(idx, tuple) else (idx,)
        dots = [k for k, i in enumerate(idx) if i is Ellipsis]
        if dots:
            k = dots[0]
            idx = idx[:k] + (slice(None),) * (4 - len(idx)) + idx[k + 1:]
        idx = idx + (slice(None),) * (3 - len(idx))
        if len(idx) > 3:
            raise IndexError(f"{len(idx)} indices for a 3-d SlabView")
        # the arrays and integers pick out of the padded slabs, then the
        # slices cut what they picked: a gather whose slice ends inside the
        # padding makes the TPU copy the whole slab (4.3 GB a chip at 2^24)
        picks = tuple(i if isinstance(i, slice) else _wrapped(i, n)
                      for i, n in zip(idx, self.shape))
        out = self.slabs[tuple(slice(None) if isinstance(i, slice) else i
                               for i in picks)]
        cut = [slice(None)] * out.ndim
        for k, at in _slice_landings(picks).items():
            start, stop, step = idx[k].indices(self.shape[k])
            cut[at] = slice(start, None if stop < 0 else stop, step)
        return out[tuple(cut)]

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.slabs, dtype=dtype)[:, :self.rows, :self.w]


def _wrapped(i, n: int) -> np.ndarray:
    """An integer or array index of an axis of logical length `n` as the
    non-negative integers it means (a boolean mask as its positions)."""
    i = np.asarray(i)
    if i.dtype == bool:
        return np.flatnonzero(i)
    return np.where(i < 0, i + n, i)


def _slice_landings(idx: tuple) -> dict:
    """{axis of a slice in `idx`: its axis in the indexed result}, by
    NumPy's rule: the picked axes take the place of adjacent picks, and
    go first when the picks are apart."""
    picked = [k for k, i in enumerate(idx) if not isinstance(i, slice)]
    nb = len(np.broadcast_shapes(*(np.shape(idx[k]) for k in picked)))
    together = picked == list(range(picked[0], picked[-1] + 1)) \
        if picked else True
    at, out = (0 if together else nb), {}
    for k, i in enumerate(idx):
        if isinstance(i, slice):
            out[k], at = at, at + 1
        elif together and k == picked[0]:
            at += nb
    return out


@functools.lru_cache(maxsize=32)
def _block_writer(mesh: Mesh):
    """(zeros(shape, dtype) -> slabs, write(slabs, block (P,B,wp), lo) ->
    slabs with every shard's rows [lo, lo+B) replaced by its block, in
    place: the slabs are donated)."""
    sh = NamedSharding(mesh, PS(AXIS))
    zeros = jax.jit(lambda shape, dt: jnp.zeros(shape, dt),
                    static_argnums=(0, 1), out_shardings=sh)
    write = jax.jit(lambda slabs, block, lo: lax.dynamic_update_slice(
        slabs, block, (0, lo, 0)), out_shardings=sh, donate_argnums=0)
    return zeros, write


def _slabs_for(store, mesh: Mesh, np_dtype, backend) -> "jnp.ndarray":
    """The sharded residency: a (P, rows, words) array (`slab_shape`)
    placed so each mesh shard materializes exactly the chunk rows it homes
    (padding rows and lanes are zeros nobody reads). Cached on the store
    keyed by dtype and pinned to `store.version` — any host mutation
    invalidates it. A miss stages `UPLOAD_ROWS` rows of every shard at a
    time straight from the host values (no whole-table copy on the host or
    in transit), and counts each block in `backend.transfer_bytes`."""
    ent = store.__dict__.get("_spmd_values", {}).get(str(np_dtype))
    if ent is not None and ent[0] == store.version:
        return ent[1]
    lay = store.shard_layout()
    rows, w, K = lay.slab_rows, store.value_width, store.num_keys
    shape = slab_shape(rows, w)
    step = min(UPLOAD_ROWS, rows)
    zeros, write = _block_writer(mesh)
    dev = zeros((store.P,) + shape, np.dtype(np_dtype))
    for lo in range(0, rows, step):
        keys = lay.slab_keys[:, lo:lo + step]
        block = np.zeros(keys.shape + shape[1:], dtype=np_dtype)
        live = keys < K
        block[live, :w] = store.values[keys[live]]
        with span("backend.upload", bytes=block.nbytes):
            dev = write(dev, jax.device_put(block, NamedSharding(
                mesh, PS(AXIS))), np.int32(lo))
        backend.transfer_bytes += block.nbytes
    _pin_slabs(store, np_dtype, dev)
    return dev


def _pin_slabs(store, np_dtype, dev, current: bool = True) -> None:
    """Make `dev` the resident slabs. `current=False` holds them without
    vouching for them: they carry writes the host copy has not taken yet,
    so the next stage re-stages unless `apply_writes` pins them again."""
    store.__dict__.setdefault("_spmd_values", {})[str(np_dtype)] = (
        store.version if current else None, dev)


def _full_replicas(replicas) -> np.ndarray:
    """The hot ids held by EVERY machine: only those join the replica slab
    (a partial holders bitmap falls back to the owner fetch — values are
    identical either way)."""
    full = replicas.holders.all(axis=1)
    return np.asarray(replicas.hot_ids, dtype=np.int64)[full]


def _replica_arrays(store, replicas, np_dtype, backend):
    """Device-side replica residency: (hot ids on the host, (rep_own (H,),
    rep_slot (H,), rep_slab (H, w)) on the device) with H pow2-padded
    (padding: owner P, slot -1), or (None, None) when nothing is fully
    replicated. Cached per directory object + store version; a miss counts
    its upload in `backend.transfer_bytes`."""
    if replicas is None or replicas.hot_ids.size == 0:
        return None, None
    ids = _full_replicas(replicas)
    if ids.size == 0:
        return None, None
    sig = (id(replicas), ids.size)
    cache = store.__dict__.setdefault("_spmd_replicas", {})
    ent = cache.get(str(np_dtype))
    if ent is not None and ent[0] == store.version and ent[1] == sig:
        return ids, ent[2]
    lay = store.shard_layout()
    H = _bucket(ids.size)
    rep_own = np.full(H, store.P, dtype=np.int32)
    rep_own[:ids.size] = lay.owner[ids]
    rep_slot = np.full(H, -1, dtype=np.int32)
    rep_slot[:ids.size] = lay.local_slot[ids]
    rep_slab = np.zeros((H, store.value_width), dtype=np_dtype)
    rep_slab[:ids.size] = store.values[ids]
    out = (jnp.asarray(rep_own), jnp.asarray(rep_slot), jnp.asarray(rep_slab))
    backend.transfer_bytes += rep_own.nbytes + rep_slot.nbytes + rep_slab.nbytes
    cache[str(np_dtype)] = (store.version, sig, out)
    return ids, out


def _pin_replicas(store, replicas, np_dtype, arrays) -> None:
    sig = (id(replicas), int(_full_replicas(replicas).size))
    store.__dict__.setdefault("_spmd_replicas", {})[str(np_dtype)] = (
        store.version, sig, arrays)


def _replica_slot(ids: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Each key's row in the replica slab (ids in slab order), -1 where the
    key is not replicated (or is -1)."""
    order = np.argsort(ids, kind="stable")
    pos = np.clip(np.searchsorted(ids[order], keys), 0, ids.size - 1)
    return np.where(ids[order][pos] == keys, order[pos], -1).astype(np.int32)


# ---------------------------------------------------------------------------
# the per-shard stage body
# ---------------------------------------------------------------------------
def _write_combine(u, seg, nseg, order, rowid):
    """Definition 2 case (iv) across shards: per segment, the row with the
    lowest `order` wins, ties broken by the lowest *global* task row id —
    exactly the numpy oracle's lexsort semantics, so a priority tie resolves
    identically no matter which shard each contender executed on. Returns
    (winner rows, winning order per segment, winning rowid per segment)."""
    n = u.shape[0]
    segc = jnp.clip(seg, 0, max(nseg - 1, 0))
    live = seg < nseg
    win_o = jnp.full(nseg, _IMAX, jnp.int32).at[seg].min(
        jnp.where(live, order, _IMAX), mode="drop")
    tie = live & (order == win_o[segc])
    win_r = jnp.full(nseg, _IMAX, jnp.int32).at[
        jnp.where(tie, seg, nseg)].min(rowid, mode="drop")
    final = tie & (rowid == win_r[segc])
    rows_idx = jnp.full(nseg, n, jnp.int32).at[
        jnp.where(final, seg, nseg)].min(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return u[jnp.clip(rows_idx, 0, max(n - 1, 0))], win_o, win_r


def _local_combine(u, seg, nseg, merge_name, order, rowid):
    if merge_name == "write":
        return _write_combine(u, seg, nseg, order, rowid)
    combined = _segment_combine(u, seg, nseg, merge_name, order)
    zeros = jnp.zeros(nseg, jnp.int32)
    return combined, zeros, zeros


def _segments(ids, live, sentinel):
    """Sorted distinct live `ids` (padded with `sentinel`) and each row's
    segment among them (n for a dead row): the static-shape group-by both
    combine sides of Phase 4 use."""
    n = ids.shape[0]
    key = jnp.where(live, ids, sentinel)
    uniq = jnp.unique(key, size=n, fill_value=sentinel)
    seg = jnp.where(live, jnp.searchsorted(uniq, key).astype(jnp.int32), n)
    return uniq, seg


def _apply_at(slab, rows_at, combined, merge_name, w):
    """⊙-apply `combined` (w words a row, or one to broadcast) into the
    slab rows `rows_at` (sorted, distinct; rows past the slab are dropped)
    — a scatter of whole lane-padded rows over the written rows only, in
    place when the slab is donated. The padding lanes take zeros, which
    every ⊙ leaves at zero."""
    n, wp = combined.shape[0], slab.shape[1]
    combined = jnp.pad(jnp.broadcast_to(combined, (n, w)),
                       ((0, 0), (0, wp - w)))
    at = slab.at[rows_at]
    if merge_name == "add":
        return at.add(combined, mode="drop")
    if merge_name == "min":
        return at.min(combined, mode="drop")
    if merge_name in ("max", "or"):
        return at.max(combined, mode="drop")
    if merge_name == "write":
        return at.set(combined, mode="drop")
    raise KeyError(f"merge op {merge_name!r} has no sharded apply")


def build_stage_program(mesh, *, f, fwd_mask: bool, ragged: bool,
                        merge_name: str, combine: bool, want_update: bool,
                        want_result: bool, P: int, K_max: int, T: int,
                        Np: int, A: int, H: int, w: int, np_dtype):
    """Compile one sharded stage executable (cached by the backend per
    static signature). Array arguments, all leading-(P,·) except the
    replicated replica arrays:

      slabs (P,K_max,words) sharded and donated (`slab_shape`); ctx (P,T,cw);
      valid (P,T);
      wk/wown/wslot/order/grow (P,T) int32 (write key, its owner shard and
      slab row); pown/pslot/prep (P,Np) int32 (each pair's owner shard —
      P when inactive —, slab row and replica-slab row, -1 for none;
      flat: Np==T, pair==task); ragged adds prow/pcol (P,Np) + mask
      (P,T,A); H>0 adds rep_own/rep_slot (H,) and rep_slab (H,w).
    """
    dt = jnp.dtype(np_dtype)

    def body(slabs, ctx, valid, wk, wown, wslot, order, grow, pown, pslot,
             prep, prow, pcol, mask, rep_own, rep_slot, rep_slab):
        slab, ctx, valid = slabs[0], ctx[0], valid[0]
        wk, wown, wslot = wk[0], wown[0], wslot[0]
        order, grow = order[0], grow[0]
        pown, pslot, prep = pown[0], pslot[0], prep[0]
        me = lax.axis_index(AXIS).astype(jnp.int32)

        # ---- Phase 1: contention detection (owner histogram + psum) -------
        with jax.named_scope("phase1_histogram"):
            if ragged:
                prow_l, pcol_l, mask_l = prow[0], pcol[0], mask[0]
                active = pown < P
            else:
                active = valid & (pown < P)
            by_owner = jnp.zeros(P + 1, jnp.int32).at[
                jnp.where(active, pown, P)].add(1)
            owned_demand = lax.psum(by_owner[:P], AXIS)[me]

        # ---- Phase 2: push-pull co-location (replica-local or a2a fetch) --
        with jax.named_scope("phase2_fetch_a2a"):
            if H > 0:
                rep_hit = active & (prep >= 0)
            else:
                rep_hit = jnp.zeros_like(active)
            need = active & ~rep_hit
            routing = bucket_routing(jnp.where(need, pown, P), P, Np,
                                     active=need)
            req = scatter_to_buckets(pslot[:, None], routing, P, Np, fill=-1)
            recv = _a2a(req)[..., 0].reshape(P * Np)
            r_ok = recv >= 0
            reply = jnp.where(r_ok[:, None],
                              slab[jnp.clip(recv, 0, K_max - 1)][:, :w],
                              jnp.zeros((), dt)).reshape(P, Np, w)
            fetched = gather_from_buckets(_a2a(reply), routing, Np)
            if H > 0:
                fetched = jnp.where(rep_hit[:, None],
                                    rep_slab[jnp.clip(prep, 0, H - 1)],
                                    fetched)

        # ---- Phase 3: local execution -------------------------------------
        with jax.named_scope("phase3_gather_lambda"):
            if ragged:
                gathered = jnp.zeros((T, A, w), dt).at[prow_l, pcol_l].set(
                    jnp.where(active[:, None], fetched, 0), mode="drop")
                out = f(ctx, gathered, mask_l) if fwd_mask else f(ctx, gathered)
            else:
                gathered = jnp.where(active[:, None], fetched, jnp.zeros((), dt))
                out = f(ctx, gathered, active) if fwd_mask else f(ctx, gathered)
            out = dict(out) if out is not None else {}

            res = out.get("result") if want_result else None
            # absent results travel as a zero-width dummy; a 1-D (T,) result
            # keeps its rank (the host tells the two apart by ndim, so the
            # caller-visible shape matches the oracle exactly)
            res = jnp.zeros((T, 0), dt) if res is None else jnp.asarray(res)
            upd_raw = out.get("update")

        # ---- Phase 4: local ⊗-combine, a2a to owners, owner-side ⊙ --------
        n_comb_sent = n_comb_recv = jnp.zeros((), jnp.int32)
        writer = valid & (wk >= 0)
        applied = combine and upd_raw is not None
        new_slab = slab
        if applied:
            with jax.named_scope("phase4_combine"):
                u = _as_update_rows(upd_raw, T, dt)
                uw = u.shape[1]
                ukeys, seg = _segments(wk, writer, _IMAX)
                combined, pay_o, pay_r = _local_combine(
                    u, seg, T, merge_name, order, grow)
                # every writer of a segment names the same owner and row
                u_own = jnp.full(T, P, jnp.int32).at[seg].set(wown,
                                                              mode="drop")
                u_slot = jnp.full(T, -1, jnp.int32).at[seg].set(wslot,
                                                                mode="drop")
            with jax.named_scope("phase4_a2a"):
                uactive = ukeys < _IMAX
                routing2 = bucket_routing(u_own, P, T, active=uactive)
                r_rows = _a2a(scatter_to_buckets(combined, routing2, P, T))
                r_slot = _a2a(scatter_to_buckets(
                    u_slot[:, None], routing2, P, T,
                    fill=-1))[..., 0].reshape(P * T)
                r_ord = _a2a(scatter_to_buckets(
                    pay_o[:, None], routing2, P, T,
                    fill=_IMAX))[..., 0].reshape(P * T)
                r_row = _a2a(scatter_to_buckets(
                    pay_r[:, None], routing2, P, T,
                    fill=_IMAX))[..., 0].reshape(P * T)
            with jax.named_scope("phase4_apply"):
                r_live = r_slot >= 0
                rows_at, seg2 = _segments(r_slot, r_live, K_max)
                comb2, _, _ = _local_combine(r_rows.reshape(P * T, uw), seg2,
                                             P * T, merge_name, r_ord, r_row)
                new_slab = _apply_at(slab, rows_at, comb2, merge_name, w)
                n_comb_sent = jnp.sum(uactive.astype(jnp.int32))
                n_comb_recv = jnp.sum(r_live.astype(jnp.int32))

        # ---- replica write-through: owners broadcast post-apply rows ------
        with jax.named_scope("phase4_apply"):
            if H > 0 and applied:
                at = jnp.clip(jnp.searchsorted(rows_at, rep_slot), 0,
                              P * T - 1)
                rep_touch = (rep_own == me) & (rows_at[at] == rep_slot)
                contrib = jnp.where(
                    rep_touch[:, None],
                    new_slab[jnp.clip(rep_slot, 0, K_max - 1)][:, :w],
                    jnp.zeros((), dt))
                tmask = lax.psum(rep_touch.astype(jnp.int32), AXIS) > 0
                rep_new = jnp.where(tmask[:, None], lax.psum(contrib, AXIS),
                                    rep_slab)
            else:
                rep_new = rep_slab

        if upd_raw is not None and want_update:
            upd = _as_update_rows(upd_raw, T, dt)
        elif upd_raw is not None and combine:
            # zero rows, real width: the host learns the update width (the
            # cost model charges by it) without transferring any floats
            upd = _as_update_rows(upd_raw, T, dt)[:0]
        else:
            upd = jnp.zeros((T, 0), dt)
        stats = jnp.stack([
            jnp.sum(valid.astype(jnp.int32)),
            jnp.sum(active.astype(jnp.int32)),
            jnp.sum(need.astype(jnp.int32)),
            jnp.sum(r_ok.astype(jnp.int32)),
            jnp.sum(rep_hit.astype(jnp.int32)),
            jnp.sum(writer.astype(jnp.int32)),
            n_comb_sent, n_comb_recv,
            owned_demand.astype(jnp.int32),
        ])
        # results and updates leave as the shards' rows stacked, (P*T, ...):
        # a TPU keeps such arrays row-major, where a (1, T, w) block per
        # shard would come home through a relayout
        return (res, upd, new_slab[None], rep_new, stats[None])

    sh = PS(AXIS)
    rep = PS()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sh,) * 14 + (rep,) * 3,
        out_specs=(sh, sh, sh, rep, sh))
    return jax.jit(fn, donate_argnums=0)


def exchange_bytes(P: int, Np: int, T: int, w: int, uw: int, itemsize: int,
                   phase4: bool) -> int:
    """Bytes of one stage's all-to-all send buffers as compiled (padded to
    their pow2 capacities), summed over the P shards: Phase 2's slab-row
    requests (int32) and replies (w words), and, when the stage applies
    writes, Phase 4's combined rows (uw words) with their slab row, order
    and row id (int32 each)."""
    per_shard = P * Np * (4 + w * itemsize)
    if phase4:
        per_shard += P * T * (uw * itemsize + 3 * 4)
    return P * per_shard


@functools.lru_cache(maxsize=32)
def _row_gather(mesh: Mesh, w: int):
    """(slabs (P,K_max,wp), rows (P,B)) -> (P*B,w): each shard reads its own
    slab rows — the write-back's fetch, local to every shard."""
    def body(slabs, rows):
        slab = slabs[0]
        return slab[jnp.clip(rows[0], 0, slab.shape[0] - 1)][:, :w]

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(PS(AXIS),) * 2,
                                 out_specs=PS(AXIS)))


# ---------------------------------------------------------------------------
# host-side stage driver
# ---------------------------------------------------------------------------
class ShardPlacement(NamedTuple):
    """Host layout of one batch over the mesh: task t lives on
    `shard[t]` at slot `slot[t]` of a (P, T_cap) block."""

    shard: np.ndarray
    slot: np.ndarray
    T_cap: int


def place_tasks(exec_site: np.ndarray, P: int) -> ShardPlacement:
    exec_site = np.asarray(exec_site, dtype=np.int64)
    slot, counts = stable_bucket_slots(exec_site, P)
    return ShardPlacement(shard=exec_site, slot=slot,
                          T_cap=_bucket(int(counts.max(initial=1))))


def _key_maps(lay, keys: np.ndarray, P: int):
    """(owner shard, slab row) of each key as int32; (P, -1) where the key
    is -1."""
    has = keys >= 0
    k = np.where(has, keys, 0)
    own = np.where(has, lay.owner[k], P).astype(np.int32)
    slot = np.where(has, lay.local_slot[k], -1).astype(np.int32)
    return own, slot


def run_sharded_stage(backend, tasks, store, f, merge,
                      want_result: bool, combine: bool, want_update: bool,
                      exec_site: Optional[np.ndarray],
                      replicas) -> Dict[str, object]:
    """Execute one stage's numerics over the device mesh. Returns the
    backend-facing dict: host `result`/`update` rows (in original task
    order), the apply carry (device `new_slabs`/replica slab), the measured
    `ShardStageStats` and the stage's all-to-all bytes and rows."""
    P = store.P
    mesh = get_mesh(P)
    lay = store.shard_layout()
    np_dtype = backend._np_dtype
    n = tasks.n
    with span("backend.prepare"):
        site = tasks.origin if exec_site is None else exec_site
        pl = place_tasks(site, P)
        T = pl.T_cap

        ctx_np = np.asarray(tasks.contexts).astype(np_dtype, copy=False)
        # rank-preserving: a 1-D contexts array (TaskBatch supports it) must
        # reach the lambda as 1-D per shard, exactly as the oracle passes it
        ctx = np.zeros((P, T) + ctx_np.shape[1:], dtype=np_dtype)
        ctx[pl.shard, pl.slot] = ctx_np
        valid = np.zeros((P, T), dtype=bool)
        valid[pl.shard, pl.slot] = True
        wk = np.full((P, T), -1, dtype=np.int32)
        wk[pl.shard, pl.slot] = tasks.write_keys
        wown = np.full((P, T), P, dtype=np.int32)
        wslot = np.full((P, T), -1, dtype=np.int32)
        wown[pl.shard, pl.slot], wslot[pl.shard, pl.slot] = _key_maps(
            lay, tasks.write_keys, P)
        order = np.zeros((P, T), dtype=np.int32)
        order[pl.shard, pl.slot] = np.clip(tasks.priority, -2**31, 2**31 - 1)
        grow = np.full((P, T), n, dtype=np.int32)
        grow[pl.shard, pl.slot] = np.arange(n, dtype=np.int32)

        ragged = tasks.max_arity > 1
        A = int(tasks.max_arity) if ragged else 1
        if ragged:
            pair_shard = pl.shard[tasks.pair_task]
            pair_col = np.arange(tasks.nnz, dtype=np.int64) \
                - tasks.read_indptr[:-1][tasks.pair_task]
            pslot_ix, pcounts = stable_bucket_slots(pair_shard, P)
            Np = _bucket(int(pcounts.max(initial=1)))
            at = (pair_shard, pslot_ix)
            keys = tasks.read_indices
            prow = np.full((P, Np), T, dtype=np.int32)
            prow[at] = pl.slot[tasks.pair_task]
            pcol = np.zeros((P, Np), dtype=np.int32)
            pcol[at] = pair_col
            mask = np.zeros((P, T, A), dtype=bool)
            mask[pair_shard, pl.slot[tasks.pair_task], pair_col] = True
        else:
            Np = T
            at = (pl.shard, pl.slot)
            keys = tasks.read_keys
            prow = pcol = np.zeros((P, 1), dtype=np.int32)
            mask = np.zeros((P, 1, 1), dtype=bool)
        pown = np.full((P, Np), P, dtype=np.int32)
        pslot = np.full((P, Np), -1, dtype=np.int32)
        pown[at], pslot[at] = _key_maps(lay, keys, P)

        rep_ids, rep_dev = _replica_arrays(store, replicas, np_dtype, backend)
        H = 0 if rep_ids is None else int(rep_dev[0].shape[0])
        if H:
            prep = np.full((P, Np), -1, dtype=np.int32)
            prep[at] = _replica_slot(rep_ids, keys)
            rep_own, rep_slot, rep_slab = rep_dev
        else:
            prep = np.zeros((P, 1), dtype=np.int32)
            rep_own = rep_slot = np.zeros(1, dtype=np.int32)
            rep_slab = np.zeros((1, store.value_width), dtype=np_dtype)

    fwd = execution._accepts_mask(f)
    K_max = slab_shape(lay.slab_rows, store.value_width)[0]
    sig = (id(f), fwd, ragged, merge.name if merge is not None else None,
           combine, want_update, want_result, P, K_max, T, Np, A,
           H, store.value_width, ctx_np.shape[1:], str(np_dtype))
    prog = backend._programs.get(sig)
    if prog is None:
        prog = backend._programs[sig] = build_stage_program(
            mesh, f=f, fwd_mask=fwd, ragged=ragged,
            merge_name=merge.name if merge is not None else "add",
            combine=combine, want_update=want_update,
            want_result=want_result, P=P, K_max=K_max, T=T,
            Np=Np, A=A, H=H, w=store.value_width, np_dtype=np_dtype)

    slabs = _slabs_for(store, mesh, np_dtype, backend)
    # the program uploads its host operands: each shard its own block of
    # the per-shard ones, every shard the (dummy) replica arrays
    host_ops = (ctx, valid, wk, wown, wslot, order, grow, pown, pslot, prep,
                prow, pcol, mask)
    rep_ops = (rep_own, rep_slot, rep_slab)
    backend.transfer_bytes += sum(a.nbytes for a in host_ops) + (
        0 if H else P * sum(a.nbytes for a in rep_ops))
    try:
        with span("backend.dispatch"):
            res_d, upd_d, new_slabs, rep_new, stats_d = prog(
                slabs, *host_ops, *rep_ops)
    except UNTRACEABLE as e:
        # only an untraceable lambda is fallback-eligible (mirrors the jax
        # backend, whose try covers exactly the jitted stage call); tracing
        # failed, so nothing ran and the slabs were not donated
        raise ShardStageError(
            f"sharded stage lambda is not traceable: {e}") from e
    # the stage donated the resident slabs; its output takes their place.
    # Slabs that took writes wait for apply_writes to vouch for them.
    uw = int(upd_d.shape[-1])
    applied = combine and uw > 0
    _pin_slabs(store, np_dtype, new_slabs, current=not applied)

    stats_np = backend._fetch(stats_d)
    backend.host_syncs += 1
    stats = ShardStageStats(*(stats_np[:, i].astype(np.int64)
                              for i in range(stats_np.shape[1])))

    out: Dict[str, object] = {
        "result": None, "update": None, "new_slabs": new_slabs,
        "stats": stats, "rep_arrays": None, "update_width": uw,
        "exchange_bytes": exchange_bytes(P, Np, T, store.value_width, uw,
                                         np.dtype(np_dtype).itemsize,
                                         applied),
        "exchange_rows": int(stats.fetch_sent.sum() + stats.fetch_recv.sum()
                             + stats.combine_sent.sum())}
    if H > 0:
        out["rep_arrays"] = (rep_own, rep_slot, rep_new)
    # res_d is (P*T,) for a 1-D lambda result, (P*T, rw) otherwise
    # (rw == 0 means the lambda returned no result at all)
    for name, dev, want in (("result", res_d, want_result and (
            res_d.ndim == 1 or res_d.shape[-1] > 0)),
            ("update", upd_d, want_update and uw > 0)):
        if want:
            rows = backend._fetch(dev)
            out[name] = rows.reshape((P, T) + rows.shape[1:])[pl.shard,
                                                              pl.slot]
            backend.host_syncs += 1
    return out


def fetch_slab_rows(backend, store, slabs, keys: np.ndarray) -> np.ndarray:
    """The rows of `keys` read out of the sharded slabs to the host: each
    shard gathers its own rows into a (B,) block (B the pow2 bucket of the
    largest shard's share, so batches reuse one compiled gather), and one
    fetch brings the blocks home. Counts its transfers and one host
    sync."""
    lay = store.shard_layout()
    own = lay.owner[keys]
    pos, counts = stable_bucket_slots(own, store.P)
    at = np.zeros((store.P, _bucket(int(counts.max(initial=1)))),
                  dtype=np.int32)
    at[own, pos] = lay.local_slot[keys]
    backend.transfer_bytes += at.nbytes
    rows = backend._fetch(_row_gather(get_mesh(store.P), store.value_width)(
        slabs, at))
    backend.host_syncs += 1
    return rows.reshape(at.shape + rows.shape[1:])[own, pos]
