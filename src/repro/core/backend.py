"""Pluggable numeric execution backends: numpy oracle, jitted JAX, and the
mesh-sharded SPMD realization.

The simulation-fidelity contract (`core/engine.py`) already splits every
stage into *numerics* (one vectorized gather → lambda → ⊗-combine → ⊙-apply
pass shared by all engines) and *cost* (the forest walk that charges
words/rounds). This module makes the numeric half pluggable:

* `NumpyBackend` — the reference oracle. Exactly the pure-numpy pass in
  `core/execution.py` / `core/mergeops.py`, in float64. Every numeric claim
  in the test suite is anchored to it.
* `JaxBackend` — the per-stage loop as jit-compiled jnp code with static
  shapes (`core/jaxexec.py`): Phase-1 contention histograms dispatch to
  `repro.kernels.histogram`, the Phase-3 padded gather + lambda and the
  Phase-4 segment-combine run as one fused XLA executable (the combine
  dispatching to `repro.kernels.segment_combine`, Pallas on TPU), and the
  store's values stay device-resident between stages (a version-tracked
  cache keyed on `DataStore.version`). Values are computed in float32 by
  default — the device-native precision — and match the oracle within float
  tolerance; pass ``dtype="float64"`` (requires ``jax_enable_x64``) for
  full-precision parity.
* `SpmdBackend` — `backend="jax_spmd"`: the machines made real over a
  `shard_map` device mesh (`core/shardexec.py`). Each shard materializes
  only the chunks it homes, runs the four phases locally, and exchanges
  values / combined write-backs with bucketed power-of-two all-to-alls.
  Same parity contract as the jax backend, plus measured per-shard
  `stage_stats`.

The backend-parity contract: per-phase **words and rounds are bit-identical**
across backends, because every quantity the cost model consumes (execution
sites, written-key sets, message widths) is computed on the host by the same
code regardless of backend — only the floating-point *values* differ, within
tolerance. `tests/test_backend_parity.py` pins this for all four engines.
Backends charge nothing: `apply_writes` returns the sorted unique keys it
wrote, and the engine charges their ⊙-apply.

Lambdas under the jax backend are traced with jnp arrays; a lambda that is
not traceable (calls numpy on its inputs, data-dependent control flow) is
detected on first use — by the tracer errors JAX raises for exactly that
(`jaxexec.UNTRACEABLE`) — and permanently routed to the numpy path for that
function object; `host_stages` counts every stage that ran there. Any
other failure of a jitted stage or a kernel (a compile error, a runtime
error) raises: the device path never degrades to the host in silence.
Jitted programs are cached per (lambda object, shape signature): reuse the
same function object across stages (module-level lambdas, not per-call
closures) to avoid retracing.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import numpy as np

from . import execution
from .mergeops import MergeOp
from .registry import get_backend_cls, register_backend
from .spans import span

# merges the jitted combine path implements; anything else falls back to the
# oracle apply (still correct, just not fused)
_JAX_MERGES = ("add", "min", "max", "or", "write")


def _bucket_rows(n: int) -> int:
    """Bucketed batch size for plan-scope static shapes: the next power of
    two (floored at 16). Multi-round plans whose batch sizes drift (BFS
    frontiers) then land in O(log n) compiled executables instead of
    re-jitting every round — padding rows are no-read/no-write tasks whose
    elementwise cost is far below a recompile."""
    if n <= 16:
        return 16
    return 1 << (int(n) - 1).bit_length()


def _combine_eligibility(tasks, merge: Optional[MergeOp]):
    """Shared by both device backends: (writer rows, fuse the ⊗-combine on
    device?, hand real update rows back for the oracle apply?). Fusing
    needs a supported merge and int32-safe priorities (the jitted combine
    carries them as int32 order keys)."""
    w_rows = np.flatnonzero(tasks.write_keys >= 0)
    pr = tasks.priority
    combine = bool(
        w_rows.size and merge is not None and merge.name in _JAX_MERGES
        and int(pr.min(initial=0)) > -(2**31)
        and int(pr.max(initial=0)) < 2**31 - 1)
    return w_rows, combine, bool(w_rows.size) and not combine


@register_backend("numpy")
class NumpyBackend:
    """The reference oracle: the float64 pure-numpy pass, unchanged."""

    name = "numpy"
    # host↔device state-array transfers (results / combined write-backs /
    # plan flushes). Always 0 here — the oracle IS host-resident; the jax
    # backend counts, and `benchmarks/bench_plan.py` reports syncs/round.
    host_syncs = 0
    # bytes moved between host and device, both ways (always 0 here)
    transfer_bytes = 0
    # stages a device backend ran in the numpy oracle instead (always 0 here)
    host_stages = 0

    # -- StagePlan device-residency hooks (no-ops for the host oracle) ------
    def begin_plan(self, store) -> None:
        """Enter a plan scope over `store` (see `core/plan.py`)."""

    def end_plan(self) -> None:
        """Leave the plan scope, flushing any deferred state."""

    def plan_flush(self) -> None:
        """Make the host store copy current (no-op when nothing deferred)."""

    # -- non-blocking dispatch hooks (serve.Frontend double-buffering) -----
    def prefetch(self, tasks, store) -> None:
        """Stage the batch's device operands ahead of `execute()` without
        blocking: a serving frontend calls this from its admission thread
        for batch k+1 while batch k is still computing, so the upload rides
        the async dispatch stream instead of the executor's critical path.
        Callers must not mutate `tasks.contexts` between prefetch and
        execute. No-op for the host-resident oracle."""

    def sync(self, store=None) -> None:
        """Block until pending device work (for `store`'s cached values, if
        given) has completed — a fair timing boundary for serving/benchmark
        layers. No-op for the host-resident oracle."""

    # -- phase 3 -----------------------------------------------------------
    def execute(self, tasks, store, f: Callable, merge: Optional[MergeOp] = None,
                want_result: bool = True, exec_site=None,
                replicas=None) -> Dict[str, Optional[np.ndarray]]:
        """Run the stage numerics. `exec_site`/`replicas` describe where the
        cost model placed each task and which chunks the session has
        replicated — advisory for single-device backends (the oracle and the
        jitted pipeline compute the same values regardless), load-bearing
        for the mesh-sharded backend, which places real work by them."""
        return execution.execute(tasks, store, f)

    # -- phase 4 -----------------------------------------------------------
    def apply_writes(self, tasks, store, updates, merge: MergeOp
                     ) -> np.ndarray:
        """⊗-combine and ⊙-apply the update rows into `store`; returns the
        sorted unique keys written (empty when none were)."""
        return execution.apply_writes(tasks, store, updates, merge)

    # -- phase 1 -----------------------------------------------------------
    def key_counts(self, keys: np.ndarray, num_keys: int, weights=None
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """(unique keys, int64 counts) — the observed per-chunk demand."""
        uk, inv = np.unique(np.asarray(keys, dtype=np.int64),
                            return_inverse=True)
        if weights is None:
            rc = np.bincount(inv, minlength=uk.size).astype(np.int64)
        else:
            rc = np.bincount(inv, weights=np.asarray(weights, dtype=np.float64),
                             minlength=uk.size).astype(np.int64)
        return uk, rc

    # -- phase 2 -----------------------------------------------------------
    def argsort_stable(self, keys: np.ndarray) -> np.ndarray:
        """The routing permutation (stable, so backends agree exactly)."""
        return np.argsort(keys, kind="stable")

    # -- DistEdgeMap local combine ----------------------------------------
    def combine_by_key(self, values: np.ndarray, keys: np.ndarray,
                       num_keys: int, merge: MergeOp, order: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """⊗-combine update rows per destination key; returns
        (sorted unique keys, combined rows aligned with them)."""
        uniq, seg = np.unique(keys, return_inverse=True)
        combined = merge.combine_segments(values, seg, uniq.size, order)
        return uniq, combined


@register_backend("jax")
class JaxBackend(NumpyBackend):
    """The jitted execution path (`core/jaxexec.py` + `repro.kernels`).

    Numerics only: every cost-model input is still produced by the host code
    paths, so reports are bit-identical to the numpy backend's.
    """

    name = "jax"

    # how Phase-3/4 numerics reach the kernel tree for fused-able lambdas
    # (`core/fusedlam.FusedStageLambda`); "padded" is the legacy opt-out
    KERNEL_BACKENDS = ("auto", "fused", "interpret", "padded")

    def __init__(self, dtype: str = "float32",
                 kernel_backend: str = "auto"):
        import jax  # deferred: importing repro.core must not require jax init

        from . import jaxexec

        self._jax = jax
        self._jx = jaxexec
        self._jnp = jax.numpy
        if dtype not in ("float32", "float64"):
            raise ValueError(f"unsupported jax backend dtype {dtype!r}")
        if kernel_backend not in self.KERNEL_BACKENDS:
            raise ValueError(
                f"unsupported kernel_backend {kernel_backend!r} — pick one "
                f"of {self.KERNEL_BACKENDS}")
        # "auto"/"fused": ragged stages with a fused-able lambda run the
        # ragged-native stage_fused kernel family (Pallas on TPU, jnp CSR
        # fallback elsewhere); "interpret" additionally forces the Pallas
        # kernels through interpret mode (CPU conformance pin); "padded"
        # keeps the legacy (n, max_arity, w) padded-gather path
        self.kernel_backend = kernel_backend
        if dtype == "float64" and not jax.config.jax_enable_x64:
            raise ValueError(
                "dtype='float64' needs x64: set JAX_ENABLE_X64=1 or "
                "jax.config.update('jax_enable_x64', True) before use")
        self.dtype = dtype
        self._np_dtype = np.dtype(dtype)
        self._host_lambdas: set = set()  # ids of fns proven untraceable
        self._stash = None  # one-slot (execute → apply_writes) carry
        self._route = None  # one-slot combine_by_key routing cache
        # host↔device transfer counter (results / combined write-backs /
        # plan flushes) — what bench_plan reports as syncs-per-round
        self.host_syncs = 0
        # bytes moved between host and device: every upload (store values on
        # a cache miss, contexts, keys, edge values) and every blocking fetch
        # (results, combined write-backs, flushes, histograms)
        self.transfer_bytes = 0
        # stages whose numerics ran in the numpy oracle (untraceable lambda,
        # keys past int32): a device run that means it asserts this is 0
        self.host_stages = 0
        # StagePlan device-residency scope (core/plan.py): while a plan runs
        # over `_plan_store`, write-backs stay on device and the host copy is
        # refreshed lazily at flush points (before user callbacks, plan exit)
        self._plan_store = None
        self._plan_depth = 0
        self._plan_written: list = []
        self._plan_dirty = False

    # -- StagePlan device-residency scope -----------------------------------
    def begin_plan(self, store) -> None:
        """Enter a plan scope: batches over `store` get bucketed static
        shapes, and fused write-backs defer their host materialization."""
        if self._plan_depth == 0:
            self._plan_store = store
        self._plan_depth += 1

    def end_plan(self) -> None:
        self._plan_depth = max(self._plan_depth - 1, 0)
        if self._plan_depth == 0:
            self.plan_flush()
            self._plan_store = None

    def plan_flush(self) -> None:
        """Refresh the host store copy from the device-resident values: one
        transfer covering every chunk written since the last flush. Called
        by the plan runner before any user callback and at plan exit."""
        if not self._plan_dirty:
            return
        store = self._plan_store
        # under the plan-scope invariant this is a version-matching cache
        # hit on the deferred device buffer (the deferred apply re-pins the
        # cache after every touch())
        dv = self._device_values(store)
        wk = np.unique(np.concatenate(self._plan_written))
        self._plan_written = []
        self._plan_dirty = False
        # bucket the gather shape (duplicate-pad with wk[0]) so per-round
        # flushes of drifting write sets reuse one compiled gather instead
        # of re-specializing XLA's eager gather every round
        wk_pad = np.full(_bucket_rows(wk.size), wk[0], dtype=np.int64)
        wk_pad[:wk.size] = wk
        rows = self._fetch(dv[self._upload(wk_pad)])[:wk.size].astype(
            store.values.dtype, copy=False)
        self.host_syncs += 1
        with span("backend.writeback"):
            store.write_rows(wk, rows)
        self._remember_values(store, dv)

    def _host_stage(self, tasks, store, f):
        """Run one stage's numerics in the numpy oracle, counted."""
        if tasks.n:
            self.host_stages += 1
        self._flush_if_deferred(store)
        return execution.execute(tasks, store, f)

    def _flush_if_deferred(self, store) -> None:
        """Host code is about to read/write `store.values` directly: make
        the host copy current first."""
        if self._plan_store is store and self._plan_dirty:
            self.plan_flush()

    # -- device-resident store values --------------------------------------
    def _device_values(self, store):
        cache = store.__dict__.setdefault("_device_values", {})
        ent = cache.get(self.dtype)
        if ent is not None and ent[0] == store.version:
            return ent[1]
        with span("backend.upload",
                  bytes=store.values.size * self._np_dtype.itemsize):
            dv = self._upload(store.values.astype(self._np_dtype, copy=False))
        cache[self.dtype] = (store.version, dv)
        return dv

    def _remember_values(self, store, dv) -> None:
        store.__dict__.setdefault("_device_values", {})[self.dtype] = (
            store.version, dv)

    def resident(self, store):
        """The device array holding `store`'s values for this backend
        (None before its first stage over `store`)."""
        ent = store.__dict__.get("_device_values", {}).get(self.dtype)
        return None if ent is None else ent[1]

    def _upload(self, arr):
        """Host → device copy, counted in `transfer_bytes`."""
        dev = self._jnp.asarray(arr)
        self.transfer_bytes += dev.nbytes
        return dev

    def _fetch(self, dev) -> np.ndarray:
        """Blocking device → host read, counted in `transfer_bytes`."""
        with span("backend.fetch"):
            out = np.asarray(dev)
        self.transfer_bytes += out.nbytes
        return out

    def _di(self, arr):
        return self._upload(np.asarray(arr).astype(np.int32, copy=False))

    # -- non-blocking dispatch hooks ----------------------------------------
    def prefetch(self, tasks, store) -> None:
        """Enqueue the batch's context upload on the async dispatch stream;
        `execute()` picks the staged array up when the batch arrives
        un-padded (plan-scope bucketing re-pads, so padded paths rebuild
        from host). Only the batch-owned contexts are staged — never the
        store's values: a concurrent `write_rows` on the executor thread
        could tear that snapshot, and the executor's own `_device_values`
        is version-checked exactly to own it."""
        if tasks.n == 0:
            return
        ctx_np = np.asarray(tasks.contexts).astype(self._np_dtype, copy=False)
        tasks.__dict__["_device_ctx"] = (self.dtype, self._upload(ctx_np))

    def sync(self, store=None) -> None:
        dv = None if store is None else self.resident(store)
        if dv is not None:
            self._jax.block_until_ready(dv)

    # -- phase 3 (+ fused phase-4 ⊗) ---------------------------------------
    def execute(self, tasks, store, f: Callable, merge: Optional[MergeOp] = None,
                want_result: bool = True, exec_site=None,
                replicas=None) -> Dict[str, Optional[np.ndarray]]:
        self._stash = None
        if tasks.n == 0 or id(f) in self._host_lambdas \
                or store.num_keys >= 2**30:
            return self._host_stage(tasks, store, f)

        n = tasks.n
        with span("backend.prepare"):
            # when there ARE writers but no fused combine, the engines need
            # the real update rows for the oracle apply (want_update)
            w_rows, combine, want_update = _combine_eligibility(tasks, merge)
            pr = tasks.priority
            uniq = None
            if combine:
                uniq, seg_w = np.unique(tasks.write_keys[w_rows],
                                        return_inverse=True)
                B = _bucket_rows(w_rows.size)
                w_idx = np.full(B, n, dtype=np.int32)
                w_idx[:w_rows.size] = w_rows
                seg = np.full(B, B, dtype=np.int32)
                seg[:w_rows.size] = seg_w
                order = np.zeros(B, dtype=np.int32)
                order[:w_rows.size] = pr[w_rows]
            else:
                w_idx = np.zeros(1, dtype=np.int32)
                seg = order = w_idx
            merge_name = merge.name if combine else "add"

        # ragged batches with a fused-able lambda skip the padded gather
        # entirely: the stage_fused kernel family walks the CSR pair list
        # (no max_arity padding, no materialized intermediates). Flat
        # (arity ≤ 1) batches have no padding tax — they keep the flat path.
        if (getattr(f, "fused_spec", None) is not None
                and tasks.max_arity > 1 and self.kernel_backend != "padded"):
            try:
                return self._execute_fused(
                    tasks, store, f.fused_spec, merge, merge_name, combine,
                    want_update, want_result, w_rows)
            except self._jx.UNTRACEABLE:
                # untraceable finish epilogue: same permanent per-lambda
                # fallback as the padded path below
                self._host_lambdas.add(id(f))
                return self._host_stage(tasks, store, f)

        # plan scope: pad the batch to a bucketed static shape so rounds
        # with drifting sizes share compiled executables. Padding rows read
        # nothing, write nothing (never in w_idx), and are sliced off below
        # — sound because tasks are independent lambda-tasks by the model.
        # Flat batches only: a ragged batch's nnz-shaped CSR arrays are
        # traced arguments too, so row padding alone cannot stop a re-jit
        # and would just add copies.
        n_pad = (_bucket_rows(n) if self._plan_store is store
                 and tasks.max_arity <= 1 else n)

        dv = self._device_values(store)
        with span("backend.prepare"):
            ctx_np = np.asarray(tasks.contexts).astype(self._np_dtype,
                                                       copy=False)
            if n_pad != n:
                pad = np.zeros((n_pad,) + ctx_np.shape[1:],
                               dtype=self._np_dtype)
                pad[:n] = ctx_np
                ctx_np = pad
            if tasks.max_arity <= 1:
                keys = tasks.read_keys
                if n_pad != n:
                    kp = np.full(n_pad, -1, dtype=np.int64)
                    kp[:n] = keys
                    keys = kp
            else:
                row = tasks.pair_task
                col = np.arange(tasks.nnz, dtype=np.int64) \
                    - tasks.read_indptr[:-1][row]
                mask = np.zeros((n_pad, tasks.max_arity), dtype=bool)
                mask[row, col] = True
        with span("backend.upload"):
            pre = tasks.__dict__.pop("_device_ctx", None)
            if n_pad == n and pre is not None and pre[0] == self.dtype:
                ctx = pre[1]  # staged by prefetch(); already on device
            else:
                ctx = self._upload(ctx_np)
            tail = (self._di(w_idx), self._di(seg), self._di(order))
            if tasks.max_arity <= 1:
                args = (dv, self._di(keys), ctx) + tail
            else:
                args = (dv, self._di(tasks.read_indices), self._di(row),
                        self._di(col), self._upload(mask), ctx) + tail
        fwd = execution._accepts_mask(f)
        kw = dict(f=f, fwd_mask=fwd, merge_name=merge_name, combine=combine,
                  want_update=want_update, want_result=want_result)
        run = (self._jx.run_stage_flat if tasks.max_arity <= 1
               else self._jx.run_stage_ragged)
        try:
            with span("backend.dispatch"):
                out = run(*args, **kw)
        except self._jx.UNTRACEABLE:
            # untraceable lambda (numpy calls on tracers, data-dependent
            # control flow): route this function object to the oracle path
            # from now on — if it is genuinely broken it raises there
            self._host_lambdas.add(id(f))
            return self._host_stage(tasks, store, f)

        host: Dict[str, Optional[np.ndarray]] = {"result": None,
                                                 "update": None}
        res_dev = out.get("result")
        if res_dev is not None:
            host["result"] = self._fetch(
                res_dev[:n] if n_pad != n else res_dev)
            self.host_syncs += 1
        upd_dev = out.get("update")
        if upd_dev is not None:
            host["update"] = self._fetch(
                upd_dev[:n] if n_pad != n else upd_dev)
            self.host_syncs += 1
        combined = out.get("combined")
        if combine and combined is not None:
            # the engines only ever hand `update` back to apply_writes, and
            # the combine already happened on device — carry a zero-copy
            # shape-only placeholder instead of transferring n·w floats
            placeholder = np.broadcast_to(
                np.zeros((), dtype=self._np_dtype), (n, combined.shape[1]))
            host["update"] = placeholder
            self._stash = (id(tasks), id(placeholder), placeholder, uniq,
                           combined, merge.name, dv)
        return host

    def _execute_fused(self, tasks, store, spec, merge, merge_name: str,
                       combine: bool, want_update: bool, want_result: bool,
                       w_rows) -> Dict[str, Optional[np.ndarray]]:
        """Ragged-native stage via `jaxexec.run_stage_fused`. The CSR arrays
        are bucket-padded host-side (pad *pairs* attach to pad *tasks*, so
        real rows never see them, and per-shape jit caches stay O(log)) and
        the writer combine rides per-task segment ids — same stash/
        placeholder tail as the padded path, so `apply_writes` is shared."""
        read_op, finish = spec
        n, nnz = tasks.n, tasks.nnz
        with span("backend.prepare"):
            uniq = None
            if combine:
                uniq, seg_w = np.unique(tasks.write_keys[w_rows],
                                        return_inverse=True)
                S = _bucket_rows(w_rows.size)
            else:
                S = 1
            n_pad = _bucket_rows(n + 1)  # ≥ 1 pad task to absorb pad pairs
            nnz_pad = _bucket_rows(nnz)
            indptr_p = np.full(n_pad + 1, nnz, dtype=np.int64)
            indptr_p[:n + 1] = tasks.read_indptr
            indptr_p[n_pad] = nnz_pad  # the last pad task owns every pad pair
            indices_p = np.zeros(nnz_pad, dtype=np.int64)
            indices_p[:nnz] = tasks.read_indices
            pt_p = np.full(nnz_pad, n_pad - 1, dtype=np.int64)
            pt_p[:nnz] = tasks.pair_task
            seg_t = np.full(n_pad, S, dtype=np.int32)  # S = writes nothing
            order_t = np.zeros(n_pad, dtype=np.int32)
            if combine:
                seg_t[w_rows] = seg_w
                order_t[:n] = tasks.priority  # int32-safe per eligibility
            ctx_np = np.asarray(tasks.contexts).astype(self._np_dtype,
                                                       copy=False)
            ctx_pad = np.zeros((n_pad,) + ctx_np.shape[1:],
                               dtype=self._np_dtype)
            ctx_pad[:n] = ctx_np
        dv = self._device_values(store)
        tasks.__dict__.pop("_device_ctx", None)  # padded: restage from host
        with span("backend.upload"):
            ctx = self._upload(ctx_pad)
        # the kernel uploads its host CSR operands itself, as JAX's ints
        self.transfer_bytes += (
            self._jax.dtypes.canonicalize_dtype(np.int64).itemsize
            * (indptr_p.size + indices_p.size + pt_p.size)
            + seg_t.nbytes + order_t.nbytes)
        with span("backend.dispatch"):
            out = self._jx.run_stage_fused(
                dv, indptr_p, indices_p, pt_p, ctx, seg_t, order_t,
                num_segments=S, read_op=read_op, finish=finish,
                merge_name=merge_name, combine=combine,
                want_update=want_update, want_result=want_result,
                kernel_backend=("interpret"
                                if self.kernel_backend == "interpret"
                                else "auto"))
        host: Dict[str, Optional[np.ndarray]] = {"result": None,
                                                 "update": None}
        if out["result"] is not None:
            host["result"] = self._fetch(out["result"][:n])
            self.host_syncs += 1
        if out["update"] is not None:
            host["update"] = self._fetch(out["update"][:n])
            self.host_syncs += 1
        combined = out["combined"]
        if combine and combined is not None:
            placeholder = np.broadcast_to(
                np.zeros((), dtype=self._np_dtype), (n, combined.shape[1]))
            host["update"] = placeholder
            self._stash = (id(tasks), id(placeholder), placeholder, uniq,
                           combined, merge.name, dv)
        return host

    def _take_stash(self, tasks, updates, merge: MergeOp):
        """Shared apply_writes preamble for both device backends: coerce
        `updates` to (n, w) rows and match them against the one-slot
        execute() carry. Returns (stash, updates) — stash None means "no
        fused combine for this pair, run the oracle apply". Guards the
        sentinel: if an engine transformed our zero-strided placeholder
        (copy/slice breaks the id match), applying it as real update rows
        would silently write zeros — refuse instead."""
        stash, self._stash = self._stash, None
        updates = np.atleast_2d(np.asarray(updates))
        if updates.shape[0] != tasks.n:
            updates = updates.T
        if (stash is None or stash[0] != id(tasks)
                or stash[1] != id(updates) or stash[5] != merge.name):
            if (stash is not None and updates.size
                    and 0 in updates.strides and not updates.any()):
                raise RuntimeError(
                    f"{self.name} backend: the zero-copy update placeholder "
                    "from execute() was transformed before apply_writes (id "
                    "no longer matches the fused combine). Pass the update "
                    "array through unchanged, or use backend='numpy' for "
                    "this engine.")
            return None, updates
        return stash, updates

    # -- phase 4 ⊙ ----------------------------------------------------------
    def apply_writes(self, tasks, store, updates, merge: MergeOp
                     ) -> np.ndarray:
        if updates is None:
            return np.empty(0, dtype=np.int64)
        stash, updates = self._take_stash(tasks, updates, merge)
        if stash is None:
            self._flush_if_deferred(store)
            return execution.apply_writes(tasks, store, updates, merge)
        _, _, _, uniq, combined_dev, _, dv = stash
        if uniq.size == 0:
            return uniq
        # device-side ⊙-apply (no full re-upload next stage); padding keys
        # are ascending out-of-range rows, so the scatter sees sorted unique
        # indices and is dropped past num_keys
        B = combined_dev.shape[0]
        uniq_pad = np.concatenate([
            uniq, np.arange(store.num_keys, store.num_keys + (B - uniq.size),
                            dtype=np.int64)])
        uniq_dev = self._di(uniq_pad)
        with span("backend.dispatch"):
            new_dv = self._jx.apply_rows(dv, uniq_dev, combined_dev,
                                         merge_name=merge.name)
        if self._plan_store is store:
            # plan scope: the write-back stays device-resident — the host
            # copy is refreshed at the next flush point (before any user
            # callback, or at plan exit), not per stage
            store.touch()
            self._remember_values(store, new_dv)
            self._plan_written.append(uniq)
            self._plan_dirty = True
            return uniq
        # authoritative host apply (store dtype), exactly the oracle's ⊙
        with span("backend.writeback"):
            combined = self._fetch(combined_dev)[:uniq.size].astype(
                store.values.dtype, copy=False)
            self.host_syncs += 1
            store.write_rows(uniq, merge.apply(store.values[uniq], combined))
        self._remember_values(store, new_dv)
        return uniq

    # -- phase 1 ------------------------------------------------------------
    def key_counts(self, keys: np.ndarray, num_keys: int, weights=None
                   ) -> Tuple[np.ndarray, np.ndarray]:
        keys = np.asarray(keys, dtype=np.int64)
        # dense demand: the kernels.histogram scatter (Pallas on TPU);
        # sparse keys over a huge range: the host path (identical counts)
        if keys.size == 0 or num_keys > max(1024, 8 * keys.size) \
                or num_keys >= 2**31:
            return super().key_counts(keys, num_keys, weights)
        w = None if weights is None else self._di(np.asarray(weights))
        counts = self._fetch(self._jx.contention_counts(
            self._di(keys), int(num_keys), weights=w,
            kernel_backend=("interpret"
                            if self.kernel_backend == "interpret"
                            else "auto")))
        uk = np.flatnonzero(counts)
        return uk.astype(np.int64), counts[uk].astype(np.int64)

    # -- phase 2 ------------------------------------------------------------
    def argsort_stable(self, keys: np.ndarray) -> np.ndarray:
        return self._fetch(
            self._jx.stable_argsort(self._upload(keys))
        ).astype(np.int64)

    # -- DistEdgeMap local combine ------------------------------------------
    def combine_by_key(self, values, keys, num_keys, merge: MergeOp, order):
        """Add-combines over a *repeated* key set (PageRank re-reduces the
        same edge list every round) run on device as a sorted segment sum
        over the cached routing permutation; everything else — first
        sighting of a key set, non-add merges, tiny batches — uses the
        oracle path. The returned key list is identical either way;
        combined sums agree within float32 tolerance."""
        if merge.name == "add" and keys.size >= 4096 and num_keys < 2**31:
            with span("backend.route"):
                rt = self._route
                seen = (rt is not None and rt[0].size == keys.size
                        and np.array_equal(rt[0], keys))
                if seen and len(rt) == 1:
                    # second sighting: the key set repeats — now the argsort
                    # investment pays off (a one-shot key set never sorts
                    # twice, it only pays the O(m) copy + compare)
                    perm = np.argsort(keys, kind="stable")
                    uniq, seg = np.unique(keys[perm], return_inverse=True)
                    rt = self._route = (rt[0], self._di(perm), self._di(seg),
                                        uniq.astype(np.int64))
            if seen:
                vals = np.asarray(values).astype(self._np_dtype, copy=False)
                with span("backend.upload", bytes=vals.nbytes):
                    vals_dev = self._upload(vals)
                with span("backend.dispatch"):
                    dev = self._jx.sorted_segment_sum(
                        vals_dev, rt[1], rt[2], num_segments=rt[3].size)
                self.host_syncs += 1
                return rt[3].copy(), self._fetch(dev).astype(np.float64)
            self._route = (keys.copy(),)  # candidate; build routing if seen again
        return super().combine_by_key(values, keys, num_keys, merge, order)


@register_backend("jax_spmd")
class SpmdBackend(JaxBackend):
    """The mesh-sharded SPMD execution backend (`core/shardexec.py`).

    Machines become real: a 1-D `shard_map` device mesh with one shard per
    machine, each materializing only the `DataStore` chunks it homes (plus
    the session's `ReplicaSet` entries) and executing only the tasks the
    cost model placed on it (`exec_site`). Phase 1 is a per-shard histogram
    + `psum`; Phases 2/4 move values and ⊗-combined write-backs with
    bucketed power-of-two ragged all-to-alls; replicated chunks are read
    from a shard-local slab and write-through-refreshed by a masked `psum`.

    The parity contract is unchanged: cost-model inputs are host-computed
    by the same code as the oracle (per-phase words/rounds bit-identical),
    values match the single-device jax backend within float tolerance. On
    CPU, run with ``XLA_FLAGS=--xla_force_host_platform_device_count=P`` —
    requesting a store with more machines than visible devices fails
    loudly (`shardexec.get_mesh`).

    `stage_stats` accumulates one `ShardStageStats` per sharded stage: what
    the mesh *measured* (tasks placed, all-to-all rows, replica-local
    reads), the executed counterpart of `SessionReport.per_machine()`.
    """

    name = "jax_spmd"

    def __init__(self, dtype: str = "float32",
                 kernel_backend: str = "auto"):
        # kernel_backend reaches the Phase-1 histogram dispatch; the sharded
        # Phase-3/4 stage program traces fused-able lambdas through their
        # generic padded realization (per-shard pair lists are not
        # host-visible), so stage_fused routing stays a single-device win
        super().__init__(dtype=dtype, kernel_backend=kernel_backend)
        from . import shardexec

        self._sx = shardexec
        self._programs: dict = {}  # compiled stage per (lambda, shape sig)
        self.stage_stats: list = []
        # the stages' all-to-all send buffers: their bytes as compiled
        # (padded), summed over shards, and the live rows they carried
        # (requests, replies, combined write-backs)
        self.exchange_bytes = 0
        self.exchange_rows = 0

    # -- fail-fast device-count validation ----------------------------------
    def validate_machines(self, P: int) -> None:
        """Raise loudly when the mesh cannot give every machine a device
        (called by sessions at construction; `execute` re-checks)."""
        self._sx.get_mesh(int(P))

    def reset_stats(self) -> list:
        out, self.stage_stats = self.stage_stats, []
        return out

    def _slabs(self, store):
        ent = store.__dict__.get("_spmd_values", {}).get(str(self._np_dtype))
        return None if ent is None else ent[1]

    def resident(self, store):
        """The (P, slab_rows, w) values sharded over the mesh — shard m holds
        the chunks machine m homes (None before the first stage) — as a
        `shardexec.SlabView` of the padded device slabs: for reading, not
        for the stage path."""
        slabs = self._slabs(store)
        return None if slabs is None else self._sx.SlabView(
            slabs, store.shard_layout().slab_rows, store.value_width)

    def sync(self, store=None) -> None:
        slabs = None if store is None else self._slabs(store)
        if slabs is not None:
            self._jax.block_until_ready(slabs)

    def prefetch(self, tasks, store) -> None:
        """Sharded stages materialize per-shard operands inside the stage
        program from the host copy — there is no whole-batch device upload
        to stage ahead, so this stays a no-op."""

    # -- phase 3 (sharded) + fused phase-4 ----------------------------------
    def execute(self, tasks, store, f: Callable, merge: Optional[MergeOp] = None,
                want_result: bool = True, exec_site=None,
                replicas=None) -> Dict[str, Optional[np.ndarray]]:
        self._stash = None
        self._sx.get_mesh(store.P)  # device-count failure must not degrade
        if tasks.n == 0 or id(f) in self._host_lambdas \
                or store.num_keys >= 2**30:
            return self._host_stage(tasks, store, f)
        w_rows, combine, want_update = _combine_eligibility(tasks, merge)
        self._flush_if_deferred(store)  # slabs materialize from host values
        try:
            out = self._sx.run_sharded_stage(
                self, tasks, store, f, merge, want_result, combine,
                want_update, exec_site, replicas)
        except self._sx.ShardStageError:
            # untraceable lambda: permanently route this function object to
            # the oracle path (genuinely broken lambdas raise there, with a
            # host traceback). Compile/runtime failures of the stage program
            # and host-side placement/layout failures are NOT caught — they
            # propagate as the bugs they are instead of silently unsharding
            # the run.
            self._host_lambdas.add(id(f))
            return self._host_stage(tasks, store, f)
        self.stage_stats.append(out["stats"])
        self.exchange_bytes += out["exchange_bytes"]
        self.exchange_rows += out["exchange_rows"]
        host: Dict[str, Optional[np.ndarray]] = {"result": out["result"],
                                                 "update": out["update"]}
        # update_width == 0 means the lambda returned no "update" at all —
        # then there is nothing to combine and the engine must see None,
        # exactly as the oracle would
        if combine and out["update_width"] > 0:
            uniq = np.unique(tasks.write_keys[w_rows])
            placeholder = np.broadcast_to(
                np.zeros((), dtype=self._np_dtype),
                (tasks.n, out["update_width"]))
            host["update"] = placeholder
            self._stash = (id(tasks), id(placeholder), placeholder, uniq,
                           out["new_slabs"], merge.name, out["rep_arrays"],
                           replicas)
        return host

    # -- phase 4 ⊙ (owner shards already applied; host copy catches up) ------
    def apply_writes(self, tasks, store, updates, merge: MergeOp
                     ) -> np.ndarray:
        if updates is None:
            return np.empty(0, dtype=np.int64)
        stash, updates = self._take_stash(tasks, updates, merge)
        if stash is None:
            self._flush_if_deferred(store)
            return execution.apply_writes(tasks, store, updates, merge)
        _, _, _, uniq, new_slabs, _, rep_arrays, replicas = stash
        if uniq.size == 0:
            return uniq
        # the owner shards already ⊙-applied to their slabs inside the
        # stage program; the authoritative host copy catches up with the
        # written rows, each shard reading its own
        with span("backend.writeback"):
            rows = self._sx.fetch_slab_rows(self, store, new_slabs, uniq)
            store.write_rows(uniq, rows.astype(store.values.dtype,
                                               copy=False))
        self._sx._pin_slabs(store, self._np_dtype, new_slabs)
        if rep_arrays is not None and replicas is not None:
            self._sx._pin_replicas(store, replicas, self._np_dtype,
                                   rep_arrays)
        return uniq


def make_backend(spec, *, kernel_backend: Optional[str] = None
                 ) -> NumpyBackend:
    """Coerce a user-facing `backend=` spec into a backend instance.

    None/"numpy" → the shared numpy oracle; "jax" → a `JaxBackend`
    (float32); "jax_spmd" → a `SpmdBackend` (float32, one mesh shard per
    machine); an existing backend instance passes through (shared device
    caches across sessions). `kernel_backend` selects how fused-able
    lambdas reach the kernel tree ("auto"/"fused"/"interpret"/"padded",
    see `JaxBackend`) and therefore needs a device backend.
    """
    if spec is None or spec == "numpy":
        if kernel_backend is not None:
            raise ValueError(
                f"kernel_backend={kernel_backend!r} needs backend='jax' or "
                "'jax_spmd' — the numpy oracle has no kernel dispatch")
        return _NUMPY
    if isinstance(spec, NumpyBackend):
        if kernel_backend is not None \
                and getattr(spec, "kernel_backend", None) != kernel_backend:
            raise ValueError(
                f"kernel_backend={kernel_backend!r} conflicts with the "
                f"passed backend instance (kernel_backend="
                f"{getattr(spec, 'kernel_backend', None)!r}) — construct "
                "the instance with the kernel_backend you want")
        return spec
    if isinstance(spec, str):
        cls = get_backend_cls(spec)
        return cls() if kernel_backend is None \
            else cls(kernel_backend=kernel_backend)
    raise TypeError(f"bad backend spec: {spec!r}")


_NUMPY = NumpyBackend()
