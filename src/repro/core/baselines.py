"""Baseline orchestration strategies (§2.3): direct-pull, direct-push, and
the sort-based MPC scheme. All share the vectorized execute/apply path with
TD-Orch (repro.core.execution) so the four engines produce bit-identical
stores — only the cost profile (and thus load balance) differs, exactly the
comparison in §4/Fig. 5.

Ragged multi-get batches: each (task, requested-key) pair is a fetch/ship
unit. Direct-pull fetches every pair's chunk to the task's origin; direct-push
ships the task to its *primary* key's home and pulls the remaining chunks
there; sort-based sorts by primary key and broadcasts every requested chunk
to the sorted runs. Arity-1 batches follow the exact original cost paths.

All three consult the session's hot-chunk `ReplicaSet` when one is passed
(core/replication.py): reads of chunks replicated at the consuming machine
are served locally (replica-local words), and writes to replicated chunks
are write-through-propagated home → holders — so replication benefits are
comparable engine-to-engine on the same directory.

Each engine charges through one set of routines — route, execute, write
back — that `run_stage` calls around the backend's numerics and
`estimate_cost` calls with the layout's widths in place of the lambda's.
"""
from __future__ import annotations

import numpy as np

from .backend import make_backend
from .cost import CostAccumulator
from .engine import (_L0_HEADER, OrchestrationResult, charge_apply,
                     charge_lambda_work, result_width, update_width)
from .mergeops import get_merge_op
from .registry import register_engine
from .replication import charge_write_through


def _split_replica_local(cost, store, replicas, machines, keys):
    """Drop (machine, key) pairs served by a local replica, charging their
    reads as replica-local words; returns the remaining remote pairs. Every
    engine consults the session's directory through this one helper."""
    if replicas is None or replicas.hot_ids.size == 0 or keys.size == 0:
        return machines, keys
    loc = replicas.holds(keys, machines)
    if loc.any():
        cost.local(machines[loc], store.value_width)
    return machines[~loc], keys[~loc]


def _dedup_pairs(machine: np.ndarray, keys: np.ndarray, num_keys: int):
    """Unique (machine, key) pairs -> (machines, keys)."""
    pair = machine.astype(np.int64) * np.int64(num_keys + 1) + keys
    uniq = np.unique(pair)
    return ((uniq // np.int64(num_keys + 1)).astype(np.int64),
            (uniq % np.int64(num_keys + 1)).astype(np.int64))


class _Baseline:
    """What the three baselines share: the constructor, and the stage and
    estimate compositions of each one's `_route`, `_charge_execute` and
    `_charge_write_back`."""

    name = ""

    def __init__(self, num_machines: int, work_per_task: float = 1.0,
                 work_per_pair: float = 0.0, backend=None):
        self.P = int(num_machines)
        self.work_per_task = work_per_task
        self.work_per_pair = work_per_pair
        self.backend = make_backend(backend)

    def estimate_cost(self, layout):
        """The bill of running `layout`'s stage on this engine, without the
        lambda (the `engine="auto"` contract, core/policy.py): the charge
        routines `run_stage` calls, with the layout's widths and write keys
        in place of the executed ones, and no work stealing."""
        from .policy import PhaseCostEstimate  # local: policy imports engines
        tasks, store, replicas = layout.tasks, layout.store, layout.replicas
        cost = CostAccumulator(self.P)
        site = self._route(cost, tasks, store, replicas)
        self._charge_execute(cost, tasks, site, layout.returned_width)
        self._charge_write_back(cost, tasks, store, site, *layout.writes,
                                layout.returned_width, replicas)
        return PhaseCostEstimate(self.name, cost.totals())

    def run_stage(self, tasks, store, f, write_back="add", return_results=False,
                  replicas=None):
        return self._stage(tasks, store, f, write_back, return_results,
                           replicas)

    def _stage(self, tasks, store, f, write_back, return_results, replicas,
               **route):
        merge = get_merge_op(write_back)
        cost = CostAccumulator(self.P)
        site = self._route(cost, tasks, store, replicas, **route)
        out = self.backend.execute(tasks, store, f, merge,
                                   want_result=return_results,
                                   exec_site=site, replicas=replicas)
        results = out.get("result")
        w_r = result_width(results, return_results)
        self._charge_execute(cost, tasks, site, w_r)
        updates = out.get("update")
        w_u = written = None
        if updates is not None:
            w_u = update_width(updates)
            written = self.backend.apply_writes(tasks, store, updates, merge)
        self._charge_write_back(cost, tasks, store, site, w_u, written, w_r,
                                replicas)
        return OrchestrationResult(results, cost.totals(), site, {})


@register_engine("pull")
class DirectPullEngine(_Baseline):
    """Dedup per machine, then fetch every needed chunk to the tasks (§2.3
    "Direct Pull" — the RDMA pattern). Hot chunks swamp their home machine
    with outbound B-word replies."""

    name = "pull"

    def _route(self, cost, tasks, store, replicas):
        """Every task executes at its origin; each (machine, key) pair it
        needs is fetched there once."""
        cost.begin("pull_fetch")
        if tasks.nnz:
            org, key = _dedup_pairs(tasks.origin[tasks.pair_task],
                                    tasks.read_indices, store.num_keys)
            org, key = _split_replica_local(cost, store, replicas, org, key)
            if key.size:
                hm = store.home[key]
                cost.send(org, hm, 2)  # request: key + reply address
                cost.work(hm, 1.0)
                cost.send(hm, org, store.chunk_words + 1)  # reply: the chunk
                cost.tick(2)
        cost.end()
        return tasks.origin.copy()

    def _charge_execute(self, cost, tasks, site, w_r):
        # results already live at the task's origin machine — no return traffic
        cost.begin("pull_execute")
        charge_lambda_work(cost, tasks, site, self.work_per_task,
                           self.work_per_pair)
        cost.end()

    def _charge_write_back(self, cost, tasks, store, site, w_u, written, w_r,
                           replicas):
        cost.begin("pull_write_back")
        writes = tasks.write_keys >= 0
        if w_u is not None and writes.any():
            # RDMA semantics: every task issues its own remote write — no
            # network-side combining, so a hot chunk's home machine receives
            # one message per writer (the §2.3 skew pathology).
            hm = store.home[tasks.write_keys[writes]]
            cost.send(tasks.origin[writes], hm, w_u + 1)
            cost.work(hm, 1.0)
            cost.tick()
            charge_write_through(cost, store.home, replicas,
                                 tasks.write_keys[writes], w_u)
            charge_apply(cost, store, written)
        cost.end()


@register_engine("push")
class DirectPushEngine(_Baseline):
    """Ship every task context to its chunk's home machine (§2.3 "Direct
    Push" — the RPC pattern). Hot chunks swamp their home with inbound σ-word
    contexts *and* with the execution work itself. Multi-get tasks go to
    their primary key's home and pull the remaining chunks there."""

    name = "push"

    def run_stage(self, tasks, store, f, write_back="add", return_results=False,
                  replicas=None, stealer=None):
        return self._stage(tasks, store, f, write_back, return_results,
                           replicas, stealer=stealer)

    def _route(self, cost, tasks, store, replicas, stealer=None):
        """Each task executes at its primary chunk's home (its write key's,
        if it reads nothing), or in place when that chunk is replicated at
        its origin; `stealer` reassigns oversubscribed homes' tasks first.
        The contexts then ship there, and secondary chunks are fetched."""
        B = store.chunk_words
        primary = tasks.primary_read
        reads = primary >= 0
        exec_site = tasks.origin.copy()
        exec_site[reads] = store.home[primary[reads]]
        wr_only = (~reads) & (tasks.write_keys >= 0)
        exec_site[wr_only] = store.home[tasks.write_keys[wr_only]]
        prim_local = np.zeros(tasks.n, dtype=bool)
        if replicas is not None and replicas.hot_ids.size:
            # primary chunk replicated at the origin: no RPC — the task
            # executes in place against the local replica
            prim_local[reads] = replicas.holds(primary[reads],
                                               tasks.origin[reads])
            exec_site[prim_local] = tasks.origin[prim_local]

        # ---- Phase-3 work stealing (core/elasticity.py): reassign over-
        # subscribed homes' RPCs before they are issued. The offload below
        # already carries the context to wherever exec_site points, so the
        # steal only pays for the primary chunk following the task.
        if stealer is not None:
            cost.begin("phase3_steal")
            moved, dst = stealer.plan(exec_site, eligible=~prim_local)
            if moved.size:
                src = exec_site[moved].copy()
                exec_site[moved] = dst
                rd = moved[reads[moved]]
                if rd.size:
                    mch, key = _dedup_pairs(exec_site[rd], primary[rd],
                                            store.num_keys)
                    cost.send(store.home[key], mch, B + 1)
                    cost.tick()
                stealer.note(src, dst)
            cost.end()

        cost.begin("push_offload")
        cost.send(tasks.origin, exec_site, tasks.ctx_words + _L0_HEADER)
        cost.tick()
        if prim_local.any():
            cost.local(tasks.origin[prim_local], store.value_width)
        if tasks.max_arity > 1:
            # secondary chunks fetched to the execution site, deduped per
            # (site, key) — same RPC round-trip shape as the offload
            is_primary = np.zeros(tasks.nnz, dtype=bool)
            is_primary[tasks.read_indptr[:-1][reads]] = True
            sec = np.flatnonzero(~is_primary)
            if sec.size:
                site, key = _dedup_pairs(exec_site[tasks.pair_task[sec]],
                                         tasks.read_indices[sec], store.num_keys)
                site, key = _split_replica_local(cost, store, replicas,
                                                 site, key)
                if key.size:
                    hm = store.home[key]
                    cost.send(site, hm, 2)
                    cost.send(hm, site, B + 1)
                    cost.tick(2)
        cost.end()
        return exec_site

    def _charge_execute(self, cost, tasks, site, w_r):
        cost.begin("push_execute")
        charge_lambda_work(cost, tasks, site, self.work_per_task,
                           self.work_per_pair)
        if w_r is not None:
            cost.send(site, tasks.origin, w_r + 1)
            cost.tick()
        cost.end()

    def _charge_write_back(self, cost, tasks, store, site, w_u, written, w_r,
                           replicas):
        cost.begin("push_write_back")
        writes = tasks.write_keys >= 0
        if w_u is not None and writes.any():
            cross = writes & (store.home[np.maximum(tasks.write_keys, 0)]
                              != site)
            if cross.any():
                org, key = _dedup_pairs(site[cross], tasks.write_keys[cross],
                                        store.num_keys)
                cost.send(org, store.home[key], w_u + 1)
                cost.tick()
            charge_write_through(cost, store.home, replicas,
                                 tasks.write_keys[writes], w_u)
            charge_apply(cost, store, written)
        cost.end()


@register_engine("sort")
class SortBasedEngine(_Baseline):
    """Theory-guided MPC scheme (§2.3): sort tasks by chunk address, broadcast
    chunks to the sorted runs, execute, reverse. Asymptotically optimal but
    pays ≥3 full passes over the task contexts (§3.6) — the constant factor
    TD-Orch eliminates. Modeled after KaDiS-style sample sort with perfect
    balance (generous to the baseline). Run placement uses
    `backend.argsort_stable`, which is parity-pinned across backends, so the
    bill is bit-identical on numpy/jax/jax_spmd."""

    name = "sort"

    def _route(self, cost, tasks, store, replicas):
        """Pass 1, a global sample-sort of the tasks by (primary) read key
        into P equal runs; pass 2, each chunk broadcast to every machine
        its run spans."""
        P, n = self.P, tasks.n
        primary = tasks.primary_read
        cost.begin("sort_pass")
        order = self.backend.argsort_stable(
            np.where(primary >= 0, primary, tasks.write_keys))
        block = max(1, -(-n // P))
        sorted_machine = np.empty(n, dtype=np.int64)
        sorted_machine[order] = np.arange(n, dtype=np.int64) // block
        cost.send(tasks.origin, sorted_machine, tasks.ctx_words + _L0_HEADER)
        # sample-sort bookkeeping: splitter exchange ~ P·log n words each
        cost.send(np.arange(P), np.zeros(P, dtype=np.int64), np.log2(max(n, 2)))
        cost.work(sorted_machine, np.log2(max(n / P, 2)))  # local sort work
        cost.tick(2)
        cost.end()

        cost.begin("sort_broadcast")
        if tasks.nnz:
            mch, key = _dedup_pairs(sorted_machine[tasks.pair_task],
                                    tasks.read_indices, store.num_keys)
            mch, key = _split_replica_local(cost, store, replicas, mch, key)
            if key.size:
                cost.send(store.home[key], mch, store.chunk_words + 1)
                cost.tick()
        cost.end()
        return sorted_machine

    def _charge_execute(self, cost, tasks, site, w_r):
        cost.begin("sort_execute")
        charge_lambda_work(cost, tasks, site, self.work_per_task,
                           self.work_per_pair)
        cost.end()

    def _charge_write_back(self, cost, tasks, store, site, w_u, written, w_r,
                           replicas):
        """Pass 3: reverse broadcast of the write-backs, then the reverse
        sort that returns results (or the tasks) to their origins."""
        cost.begin("sort_reverse")
        writes = tasks.write_keys >= 0
        if w_u is not None and writes.any():
            mch, key = _dedup_pairs(site[writes], tasks.write_keys[writes],
                                    store.num_keys)
            cost.send(mch, store.home[key], w_u + 1)
            charge_write_through(cost, store.home, replicas,
                                 tasks.write_keys[writes], w_u)
            charge_apply(cost, store, written)
        # tasks themselves are restored to their original order/machine
        cost.send(site, tasks.origin,
                  tasks.ctx_words + _L0_HEADER if w_r is None else w_r + 1)
        cost.tick(2)
        cost.end()
