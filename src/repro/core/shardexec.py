"""Mesh-sharded SPMD stage execution — the simulator's machines made real.

Until now every backend executed all P "machines" of a stage as one
single-device program: the cost model (`core/cost.py`) *charged* max-over-
machines work and h-relation volume, but nothing validated that the numeric
execution could actually be laid out that way. This module is that layout:
each shard of a `jax.shard_map` device mesh IS one machine — it materializes
only the `DataStore` chunks it homes (plus the session's `ReplicaSet`
entries), holds only the tasks the cost model placed on it (`exec_site`),
and runs the four phases locally with collective exchanges in between:

  Phase 1 (contention detection): per-shard histogram of requested chunk
    keys + one `psum` — the unified `jaxexec.detect_contention` primitive
    (the same call the MoE dispatch path makes).
  Phase 2 (co-location): each (task, requested-key) pair sends a request to
    the key's owner shard via a bucketed power-of-two ragged `all_to_all`
    (the pow2 padding from the plan scope, so drifting batch sizes share
    compiled executables); owners reply with the chunk rows, a second
    `all_to_all` brings them home. Pairs whose chunk is in the shard's
    replica slab never touch the wire — they read the local copy.
  Phase 3: the stage lambda runs on each shard over its local gathered
    view — exactly the `jaxexec.run_stage_*` numerics, per shard.
  Phase 4: write-backs ⊗-combine *locally* per written key, the combined
    rows ride one more `all_to_all` to the owner shards, each owner
    ⊙-applies to its slab, and written chunks that are replicated
    write-through their post-apply rows to every holder (a masked `psum` —
    the broadcast tree the hardware provides).

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
                      bucket_routing, detect_contention, gather_from_buckets,
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
def _slabs_for(store, mesh: Mesh, np_dtype, backend) -> "jnp.ndarray":
    """The sharded residency: a (P, K_max, w) array placed so each mesh
    shard materializes exactly the chunk rows it homes (padding rows are
    zeros nobody addresses). Cached on the store keyed by dtype and pinned
    to `store.version` — any host mutation invalidates it; a miss counts
    its upload in `backend.transfer_bytes`."""
    lay = store.shard_layout()
    cache = store.__dict__.setdefault("_spmd_values", {})
    ent = cache.get(str(np_dtype))
    if ent is not None and ent[0] == store.version:
        return ent[1]
    host = np.zeros((store.P, lay.slab_rows, store.value_width),
                    dtype=np_dtype)
    live = lay.slab_keys < store.num_keys
    host[live] = store.values[lay.slab_keys[live]].astype(np_dtype)
    with span("backend.upload", bytes=host.nbytes):
        dev = jax.device_put(host, NamedSharding(mesh, PS(AXIS)))
    backend.transfer_bytes += host.nbytes
    cache[str(np_dtype)] = (store.version, dev)
    return dev


def _pin_slabs(store, np_dtype, dev) -> None:
    store.__dict__.setdefault("_spmd_values", {})[str(np_dtype)] = (
        store.version, dev)


def _replica_arrays(store, replicas, np_dtype, backend):
    """Device-side replica residency: (rep_ids (H,), lookup_ext (K+1,),
    rep_slab (H, w)) with H pow2-padded (sentinel id = num_keys), or
    (None, None, None) when nothing is fully replicated. Only chunks held by
    EVERY machine join the slab (a partial holders bitmap falls back to the
    owner fetch — values are identical either way). Cached per directory
    object + store version; a miss counts its upload in
    `backend.transfer_bytes`."""
    if replicas is None or replicas.hot_ids.size == 0:
        return None, None, None
    full = replicas.holders.all(axis=1)
    ids = np.asarray(replicas.hot_ids, dtype=np.int64)[full]
    if ids.size == 0:
        return None, None, None
    K = store.num_keys
    H = _bucket(ids.size)
    sig = (id(replicas), ids.size)
    cache = store.__dict__.setdefault("_spmd_replicas", {})
    ent = cache.get(str(np_dtype))
    if ent is not None and ent[0] == store.version and ent[1] == sig:
        return ent[2]
    rep_ids = np.full(H, K, dtype=np.int32)
    rep_ids[:ids.size] = ids
    lookup = np.full(K + 1, -1, dtype=np.int32)
    lookup[ids] = np.arange(ids.size, dtype=np.int32)
    rep_slab = np.zeros((H, store.value_width), dtype=np_dtype)
    rep_slab[:ids.size] = store.values[ids].astype(np_dtype)
    out = (jnp.asarray(rep_ids), jnp.asarray(lookup), jnp.asarray(rep_slab))
    backend.transfer_bytes += rep_ids.nbytes + lookup.nbytes + rep_slab.nbytes
    cache[str(np_dtype)] = (store.version, sig, out)
    return out


def _pin_replicas(store, replicas, np_dtype, arrays) -> None:
    full = replicas.holders.all(axis=1)
    sig = (id(replicas), int(np.asarray(replicas.hot_ids)[full].size))
    store.__dict__.setdefault("_spmd_replicas", {})[str(np_dtype)] = (
        store.version, sig, arrays)


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


def _apply_to_slab(slab, combined, touched, merge_name):
    t = touched[:, None]
    if merge_name == "add":
        return slab + jnp.where(t, combined, 0)
    if merge_name == "min":
        return jnp.where(t, jnp.minimum(slab, combined), slab)
    if merge_name in ("max", "or"):
        return jnp.where(t, jnp.maximum(slab, combined), slab)
    if merge_name == "write":
        return jnp.where(t, combined, slab)
    raise KeyError(f"merge op {merge_name!r} has no sharded apply")


def build_stage_program(mesh, *, f, fwd_mask: bool, ragged: bool,
                        merge_name: str, combine: bool, want_update: bool,
                        want_result: bool, P: int, K: int, K_max: int,
                        T: int, Np: int, A: int, H: int, w: int, np_dtype):
    """Compile one sharded stage executable (cached by the backend per
    static signature). Array arguments, all leading-(P,·) except the
    replicated metadata:

      slabs (P,K_max,w) sharded; ctx (P,T,cw); valid (P,T); wk/order/grow
      (P,T) int32; pkey (P,Np) int32 (flat: Np==T, pair==task);
      ragged adds prow/pcol (P,Np) + mask (P,T,A);
      owner_ext/slot_ext (K+1,) replicated (index K = sentinel);
      H>0 adds rep_ids (H,), rep_lookup_ext (K+1,), rep_slab (H,w).
    """
    dt = jnp.dtype(np_dtype)

    def body(slabs, ctx, valid, wk, order, grow, pkey, prow, pcol, mask,
             owner_ext, slot_ext, rep_ids, rep_lookup_ext, rep_slab):
        slab, ctx, valid = slabs[0], ctx[0], valid[0]
        wk, order, grow, pkey = wk[0], order[0], grow[0], pkey[0]
        me = lax.axis_index(AXIS).astype(jnp.int32)

        # ---- Phase 1: contention detection (histogram + psum) -------------
        with jax.named_scope("phase1_histogram"):
            if ragged:
                prow_l, pcol_l, mask_l = prow[0], pcol[0], mask[0]
                active = pkey >= 0
            else:
                active = valid & (pkey >= 0)
            sent_key = jnp.where(active, pkey, K)
            gcounts = detect_contention(sent_key, K + 1, AXIS)[:K]
            owned = owner_ext[:K] == me
            owned_demand = jnp.sum(jnp.where(owned, gcounts, 0))

        # ---- Phase 2: push-pull co-location (replica-local or a2a fetch) --
        with jax.named_scope("phase2_fetch_a2a"):
            if H > 0:
                rep_slot = rep_lookup_ext[sent_key]
                rep_hit = active & (rep_slot >= 0)
            else:
                rep_hit = jnp.zeros_like(active)
            need = active & ~rep_hit
            dest = jnp.where(need, owner_ext[sent_key], P).astype(jnp.int32)
            routing = bucket_routing(dest, P, Np, active=need)
            req = scatter_to_buckets(
                slot_ext[sent_key][:, None].astype(jnp.int32), routing, P, Np,
                fill=-1)
            recv = _a2a(req)[..., 0].reshape(P * Np)
            r_ok = recv >= 0
            reply = jnp.where(r_ok[:, None],
                              slab[jnp.clip(recv, 0, K_max - 1)],
                              jnp.zeros((), dt)).reshape(P, Np, w)
            fetched = gather_from_buckets(_a2a(reply), routing, Np)
            if H > 0:
                fetched = jnp.where(rep_hit[:, None],
                                    rep_slab[jnp.clip(rep_slot, 0, H - 1)],
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
        if combine and upd_raw is not None:
            with jax.named_scope("phase4_combine"):
                u = _as_update_rows(upd_raw, T, dt)
                uw = u.shape[1]
                wkey = jnp.where(writer, wk, K)
                ukeys = jnp.unique(wkey, size=T, fill_value=K)
                seg = jnp.where(
                    writer, jnp.searchsorted(ukeys, wkey).astype(jnp.int32),
                    T)
                combined, pay_o, pay_r = _local_combine(
                    u, seg, T, merge_name, order, grow)
            with jax.named_scope("phase4_a2a"):
                uactive = ukeys < K
                dest2 = jnp.where(uactive, owner_ext[ukeys],
                                  P).astype(jnp.int32)
                routing2 = bucket_routing(dest2, P, T, active=uactive)
                r_rows = _a2a(scatter_to_buckets(combined, routing2, P, T))
                r_slot = _a2a(scatter_to_buckets(
                    slot_ext[ukeys][:, None].astype(jnp.int32), routing2, P,
                    T, fill=-1))[..., 0].reshape(P * T)
                r_ord = _a2a(scatter_to_buckets(
                    pay_o[:, None], routing2, P, T,
                    fill=_IMAX))[..., 0].reshape(P * T)
                r_row = _a2a(scatter_to_buckets(
                    pay_r[:, None], routing2, P, T,
                    fill=_IMAX))[..., 0].reshape(P * T)
            with jax.named_scope("phase4_apply"):
                r_live = r_slot >= 0
                seg2 = jnp.where(r_live, r_slot, K_max)
                comb2, _, _ = _local_combine(r_rows.reshape(P * T, uw), seg2,
                                             K_max, merge_name, r_ord, r_row)
                touched = jnp.zeros(K_max, jnp.int32).at[seg2].add(
                    1, mode="drop") > 0
                new_slab = _apply_to_slab(slab, comb2, touched, merge_name)
                n_comb_sent = jnp.sum(uactive.astype(jnp.int32))
                n_comb_recv = jnp.sum(r_live.astype(jnp.int32))
        else:
            new_slab = slab

        # ---- replica write-through: owners broadcast post-apply rows ------
        with jax.named_scope("phase4_apply"):
            if H > 0 and combine and upd_raw is not None:
                rep_live = rep_ids < K
                rep_local = jnp.clip(slot_ext[rep_ids], 0, K_max - 1)
                mine = rep_live & (owner_ext[rep_ids] == me)
                rep_touch = mine & touched[rep_local]
                contrib = jnp.where(rep_touch[:, None], new_slab[rep_local],
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
        return (res[None], upd[None], new_slab[None], rep_new, stats[None])

    sh = PS(AXIS)
    rep = PS()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sh, sh, sh, sh, sh, sh, sh, sh, sh, sh,
                  rep, rep, rep, rep, rep),
        out_specs=(sh, sh, sh, rep, sh))
    return jax.jit(fn)


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


def run_sharded_stage(backend, tasks, store, f, merge,
                      want_result: bool, combine: bool, want_update: bool,
                      exec_site: Optional[np.ndarray],
                      replicas) -> Dict[str, object]:
    """Execute one stage's numerics over the device mesh. Returns the
    backend-facing dict: host `result`/`update` rows (in original task
    order), plus the apply carry (`uniq`, device `new_slabs`/replica slab)
    and the measured `ShardStageStats`."""
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
            pslot, pcounts = stable_bucket_slots(pair_shard, P)
            Np = _bucket(int(pcounts.max(initial=1)))
            pkey = np.full((P, Np), -1, dtype=np.int32)
            pkey[pair_shard, pslot] = tasks.read_indices
            prow = np.full((P, Np), T, dtype=np.int32)
            prow[pair_shard, pslot] = pl.slot[tasks.pair_task]
            pcol = np.zeros((P, Np), dtype=np.int32)
            pcol[pair_shard, pslot] = pair_col
            mask = np.zeros((P, T, A), dtype=bool)
            mask[pair_shard, pl.slot[tasks.pair_task], pair_col] = True
        else:
            Np = T
            pkey = np.full((P, T), -1, dtype=np.int32)
            pkey[pl.shard, pl.slot] = tasks.read_keys
            prow = pcol = np.zeros((P, 1), dtype=np.int32)
            mask = np.zeros((P, 1, 1), dtype=bool)

        K = store.num_keys
        owner_ext = np.concatenate(
            [lay.owner.astype(np.int32), np.int32([P])])
        slot_ext = np.concatenate(
            [lay.local_slot.astype(np.int32), np.int32([lay.slab_rows])])
        rep_ids, rep_lookup_ext, rep_slab = _replica_arrays(
            store, replicas, np_dtype, backend)
        H = 0 if rep_ids is None else int(rep_ids.shape[0])
        if H == 0:
            rep_ids = jnp.zeros(1, jnp.int32)
            rep_lookup_ext = jnp.zeros(1, jnp.int32)
            rep_slab = jnp.zeros((1, store.value_width), np_dtype)

    fwd = execution._accepts_mask(f)
    sig = (id(f), fwd, ragged, merge.name if merge is not None else None,
           combine, want_update, want_result, P, K, lay.slab_rows, T, Np, A,
           H, store.value_width, ctx_np.shape[1:], str(np_dtype))
    prog = backend._programs.get(sig)
    if prog is None:
        prog = backend._programs[sig] = build_stage_program(
            mesh, f=f, fwd_mask=fwd, ragged=ragged,
            merge_name=merge.name if merge is not None else "add",
            combine=combine, want_update=want_update,
            want_result=want_result, P=P, K=K, K_max=lay.slab_rows, T=T,
            Np=Np, A=A, H=H, w=store.value_width, np_dtype=np_dtype)

    slabs = _slabs_for(store, mesh, np_dtype, backend)
    # the program uploads its host operands: the per-shard blocks once, the
    # replicated key maps to every shard
    host_ops = (ctx, valid, wk, order, grow, pkey, prow, pcol, mask)
    backend.transfer_bytes += (sum(a.nbytes for a in host_ops)
                               + P * (owner_ext.nbytes + slot_ext.nbytes))
    try:
        with span("backend.dispatch"):
            res_d, upd_d, new_slabs, rep_new, stats_d = prog(
                slabs, *host_ops, owner_ext, slot_ext, rep_ids,
                rep_lookup_ext, rep_slab)
    except UNTRACEABLE as e:
        # only an untraceable lambda is fallback-eligible (mirrors the jax
        # backend, whose try covers exactly the jitted stage call)
        raise ShardStageError(
            f"sharded stage lambda is not traceable: {e}") from e

    stats_np = backend._fetch(stats_d)
    backend.host_syncs += 1
    stats = ShardStageStats(*(stats_np[:, i].astype(np.int64)
                              for i in range(stats_np.shape[1])))

    out: Dict[str, object] = {"result": None, "update": None,
                              "new_slabs": new_slabs, "stats": stats,
                              "rep_arrays": None,
                              "update_width": int(upd_d.shape[-1])}
    if H > 0:
        out["rep_arrays"] = (rep_ids, rep_lookup_ext, rep_new)
    # res_d is (P, T) for a 1-D lambda result, (P, T, rw) otherwise
    # (rw == 0 means the lambda returned no result at all)
    if want_result and (res_d.ndim == 2 or res_d.shape[-1] > 0):
        out["result"] = backend._fetch(res_d)[pl.shard, pl.slot]
        backend.host_syncs += 1
    if want_update and upd_d.shape[-1] > 0:
        out["update"] = backend._fetch(upd_d)[pl.shard, pl.slot]
        backend.host_syncs += 1
    return out


def gather_slab_rows(store, new_slabs, keys: np.ndarray):
    """The post-apply rows for `keys` gathered out of the sharded slabs
    (one cross-device gather; the caller fetches them to the host)."""
    lay = store.shard_layout()
    return new_slabs[lay.owner[keys], lay.local_slot[keys]]
