"""Per-graph orchestration sessions for TDO-GP (§5).

Graph algorithms run dozens of DistEdgeMap rounds against the SAME
ingestion-time topology, so the tree machinery is session state, not
per-call state:

  * `TreeCharger` precomputes — once — the parent machine of every member of
    every C-ary source or destination tree (the heap layout over [root, m0, m1, ...] that
    `dist_edge_map` previously re-derived from the CSR on every round).
  * `GraphSession` owns both chargers for one `OrchestratedGraph` — the
    source trees' and the destination trees' — and folds every round's
    `StageReport` into one cross-round `SessionReport` (per-phase
    words/rounds/work summed), mirroring `core.session.Orchestrator` for the
    kv/orchestration side.
  * A round whose active edges are all the graph's edges (every PageRank
    round) climbs every ingest-time destination tree once, so its write-back
    bill is the same every such round: the session charges it once and adds
    it thereafter (`charge_full_writeback`). Such a round's edge set is
    the ingested edge set, in ingest order, so it reads read-only views of
    the graph's edge arrays (`full_edges`) and its compute bill, which is
    the same every such round too (`charge_full_work`).

Algorithms construct one session per run (`GraphSession(og, **opts)`) and
call `session.edge_map(...)` per round; calling `dist_edge_map` directly
still works — it borrows the graph's cached default session for the tree
machinery without recording into it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import numpy as np

from ..core.backend import make_backend
from ..core.config import resolve_session_config
from ..core.cost import CostAccumulator, SessionReport
from ..core.replication import make_replicator

VALUE_WORDS = 2  # one vertex value + vertex id per message


def _expand_csr(indptr: np.ndarray, select: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten CSR slices for `select` rows -> (flat positions, counts)."""
    counts = indptr[select + 1] - indptr[select]
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64), counts
    starts = indptr[select]
    # position r within each slice via the classic repeat/arange trick
    offs = np.repeat(np.cumsum(counts) - counts, counts)
    r = np.arange(total, dtype=np.int64) - offs
    return np.repeat(starts, counts) + r, counts


class TreeCharger:
    """Cost-charging machinery for one family of C-ary trees (§5.1).

    Each group (vertex) owns a tree whose root is the vertex's home machine
    and whose nodes are the sorted machine list storing the group's edges in
    heap layout [root, m0, m1, ...]. The parent machine of every member is
    precomputed once per session; per-round charging is then a flat gather.
    """

    def __init__(self, roots: np.ndarray, indptr: np.ndarray,
                 machines: np.ndarray, C: int):
        self.roots = np.asarray(roots, dtype=np.int64)
        self.indptr = np.asarray(indptr, dtype=np.int64)
        self.machines = np.asarray(machines, dtype=np.int64)
        self.C = int(C)
        counts = np.diff(self.indptr)
        grp = np.repeat(np.arange(counts.size, dtype=np.int64), counts)
        starts = np.repeat(self.indptr[:-1], counts)
        rank = np.arange(self.machines.size, dtype=np.int64) - starts
        parent_seq = rank // self.C
        self.parents = np.where(parent_seq == 0, self.roots[grp],
                                self.machines[starts + np.maximum(parent_seq - 1, 0)])

    def charge(self, cost: CostAccumulator, select: np.ndarray, words: float,
               upward: bool) -> int:
        """Charge one sweep of the selected groups' trees — downward = value
        broadcast (source tree), upward = write-back combine (destination
        tree). Returns the max tree height (BSP rounds)."""
        flat, counts = _expand_csr(self.indptr, select)
        if flat.size == 0:
            return 0
        child = self.machines[flat]
        parent = self.parents[flat]
        if upward:
            cost.send(child, parent, words)
        else:
            cost.send(parent, child, words)
        kmax = int(counts.max(initial=0))
        height = (int(np.ceil(np.log(kmax + 1) / np.log(max(self.C, 2)))) + 1
                  if kmax else 0)
        return height

    def direct_broadcast(self, cost: CostAccumulator, select: np.ndarray,
                         words: float) -> None:
        """T1 destination-aware broadcast: each selected group's root sends
        one copy straight to every machine in its member list (1 hop)."""
        flat, counts = _expand_csr(self.indptr, select)
        if flat.size == 0:
            return
        cost.send(np.repeat(self.roots[select], counts),
                  self.machines[flat], words)


@dataclasses.dataclass
class GraphSession:
    """A long-lived DistEdgeMap session over one orchestrated graph.

    `replication=` opts rounds driven through this session into adaptive
    hot-vertex replication (`repro.core.replication`): the session learns
    per-vertex demand — weighted by how many machines need the value each
    round — and keeps the hottest vertices' values resident everywhere, so
    their source-tree broadcasts become machine-local reads. Write-backs
    still ⊗-combine to the vertex home, then write-through to holders.

    `backend=` selects the numeric execution backend for the per-round
    edge-value combine ("numpy" — the float64 oracle, default — "jax", the
    jitted scatter of `repro.core.backend`, or "jax_spmd", which accepts
    graph rounds too and validates the device mesh against P at
    construction); cost reports are bit-identical either way.
    `kernel_backend=` forwards to the device backend's kernel dispatch
    ("auto"/"fused"/"interpret"/"padded" — see `repro.core.JaxBackend`),
    reaching any fused-able lambdas driven through this session.

    `config=` accepts the same `SessionConfig` every other front door takes;
    its shared fields (backend / kernel_backend / replication) resolve
    through the one alias table, and a kwarg that contradicts the config
    raises. Graph rounds never reach exec-site assignment or the
    Orchestrator stage boundary, so `elasticity=` in the config is rejected
    here rather than silently ignored.

    `engine=` (or `SessionConfig.engine`): tree-structured edge maps have
    no pluggable engine, so fixed engine names are irrelevant here and stay
    ignored — EXCEPT `engine="auto"`, which arms the session's per-round
    sparse/dense *mode* policy (the graph-side half of the adaptive loop,
    `repro.core.policy`): each `edge_map` round with `force_mode=None`
    estimates both propagation modes' bills exactly and picks the argmin
    under the BSP objective (with hysteresis), replacing the static Ligra
    direction threshold. Decisions land on `report.policy_decisions`, and
    decision latency is charged under the `policy` phase. Policy knobs ride
    `SessionConfig.engine_opts["policy"]` (a `PolicyConfig` kwargs dict).
    """

    og: "OrchestratedGraph"  # noqa: F821 — forward ref, avoids import cycle
    defaults: dict = dataclasses.field(default_factory=dict)
    replication: object = None  # None | True | dict | ReplicationConfig
    backend: object = None  # None/"numpy" oracle | "jax" jitted | instance
    kernel_backend: object = None  # fused-kernel dispatch (device backends)
    config: object = None  # SessionConfig | dict — the unified spelling
    replicate: object = None  # legacy alias for replication
    engine: object = None  # "auto" arms the sparse/dense mode policy

    def __post_init__(self):
        og = self.og
        cfg = resolve_session_config(
            self.config, backend=self.backend,
            kernel_backend=self.kernel_backend,
            replication=self.replication, replicate=self.replicate,
            engine=self.engine)
        if cfg.elasticity is not None:
            raise ValueError(
                "GraphSession does not support elasticity: DistEdgeMap "
                "rounds charge source/destination trees directly and never "
                "reach the Orchestrator stage boundary where migration/"
                "stealing/recovery plug in. Drive the workload through an "
                "Orchestrator (core/session.py) for elastic execution.")
        self.backend = cfg.backend
        self.kernel_backend = cfg.kernel_backend
        self.replication = cfg.replication
        # engine="auto": the per-round sparse/dense mode policy. The BSP
        # objective is what separates the modes — their propagation *volumes*
        # tie under T1 dedup (one copy per tree member either way); what
        # differs is tree depth (rounds) vs. root fan-out (max_comm), so the
        # decision needs max_comm + L·rounds, not total words.
        self.mode_policy = None
        if cfg.engine == "auto":
            from ..core.policy import StagePolicy, make_policy_config
            spec = cfg.engine_opts.get("policy")
            if spec is None or isinstance(spec, dict):
                spec = dict(spec or {})
                spec.setdefault("candidates", ("sparse", "dense"))
                spec.setdefault("objective", "bsp")
                spec.setdefault("round_latency", 4.0)
            self.mode_policy = StagePolicy(make_policy_config(spec))
        self.src_charger = TreeCharger(og.vertex_home, og.src_grp_indptr,
                                       og.src_grp_machines, og.C)
        self.replicator = make_replicator(self.replication, og.vertex_home,
                                          og.P, VALUE_WORDS)
        self.backend = make_backend(self.backend,
                                    kernel_backend=self.kernel_backend)
        check = getattr(self.backend, "validate_machines", None)
        if check is not None:
            check(og.P)
        self._report = SessionReport(og.P)
        self.stats: List = []
        # accounted rounds whose write-back charge read the ingest-time
        # destination trees (charge_full_writeback)
        self.dst_tree_reuses = 0
        self._full_writeback = {}  # dedup -> (sent, recv, rounds)
        # rounds whose frontier was every vertex and whose edges were the
        # ingested arrays (full_edges), not a per-round gather
        self.full_gather_reuses = 0

    # ------------------------------------------------------------------
    @property
    def P(self) -> int:
        return self.og.P

    @property
    def C(self) -> int:
        return self.og.C

    @property
    def report(self) -> SessionReport:
        """Cross-round cost accumulation (per-phase words/rounds/work)."""
        return self._report

    @property
    def num_rounds(self) -> int:
        return len(self.stats)

    @functools.cached_property
    def dst_charger(self) -> TreeCharger:
        """The ingest-time destination trees (built on first use: only
        rounds with every edge active read them)."""
        og = self.og
        return TreeCharger(og.vertex_home, og.dst_grp_indptr,
                           og.dst_grp_machines, og.C)

    def charge_full_writeback(self, cost: CostAccumulator,
                              dedup: bool) -> None:
        """Charge the write-back of a round whose active edges are all the
        graph's edges. Its (destination, machine) pairs are then exactly the
        ingest-time destination groups, so the bill — every group's tree
        climbing to the vertex home (dedup), or every member writing
        straight home — is the same every such round. It is charged once
        into a scratch accumulator and added thereafter: word counts are
        whole numbers far below 2**53, so the float64 sums come out the same
        as charging the messages one by one."""
        bill = self._full_writeback.get(dedup)
        if bill is None:
            og = self.og
            scratch = CostAccumulator(og.P)
            scratch.begin("writeback")
            counts = np.diff(og.dst_grp_indptr)
            if dedup:
                h = self.dst_charger.charge(scratch, np.flatnonzero(counts),
                                            VALUE_WORDS, upward=True)
                rounds = max(h, 1)
            else:
                homes = np.repeat(og.vertex_home, counts)
                scratch.send(og.dst_grp_machines, homes, VALUE_WORDS)
                rounds = 1
            ph = scratch.end()
            bill = self._full_writeback[dedup] = (ph.sent, ph.recv, rounds)
        sent, recv, rounds = bill
        cost.add_comm(sent, recv)
        cost.tick(rounds)
        self.dst_tree_reuses += 1

    @functools.cached_property
    def full_edges(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                  np.ndarray]:
        """(edge ids, sources, destinations, weights) of a round whose
        active edges are all the graph's edges, in ingest order: the same
        arrays a dense gather over a full frontier builds, as read-only
        views of the graph's arrays (ids `arange(m)`, weights all ones on an
        unweighted graph, both built once). Read-only, so a callback that
        writes into its arguments raises instead of changing the graph."""
        g = self.og.graph
        w = g.weights if g.weights is not None else np.ones(g.m)
        out = []
        for a in (np.arange(g.m, dtype=np.int64), g.src, g.dst, w):
            v = a.view()
            v.flags.writeable = False
            out.append(v)
        return tuple(out)

    @functools.cached_property
    def _edges_per_machine(self) -> np.ndarray:
        return self.og.edges_per_machine().astype(np.float64)

    def charge_full_work(self, cost: CostAccumulator, units: float) -> None:
        """Charge the edge compute of a round whose active edges are all
        the graph's edges: `units` per edge on the machine storing it, so
        each machine's bill is its ingest-time edge count times `units`.
        With whole `units` (1 or 3), every total is a whole number far below
        2**53, so the float64 sums come out the same as charging the edges
        one by one."""
        cost.work(np.arange(self.og.P), self._edges_per_machine * units)

    def ensure_replicator(self, spec=True):
        """Create the session's replicator on first use (for
        `dist_edge_map(..., replicate=...)` opt-in on a plain session).
        The first spec wins: later calls reuse the existing replicator
        (its learned histogram is the point) and ignore a differing spec."""
        if self.replicator is None:
            self.replicator = make_replicator(spec, self.og.vertex_home,
                                              self.og.P, VALUE_WORDS)
        return self.replicator

    # ------------------------------------------------------------------
    def edge_map(self, U, f, write_back, merge_value: str = "min",
                 filter_dst=None, **kw):
        """Run one DistEdgeMap round through this session, folding its stats
        and cost report into the session."""
        from .distedgemap import dist_edge_map  # local: avoids import cycle

        opts = {**self.defaults, **kw}
        nxt, st = dist_edge_map(self.og, U, f, write_back, merge_value,
                                filter_dst, session=self, **opts)
        self.stats.append(st)
        if st.report is not None:
            self._report.add(st.report)
        return nxt, st

    # ------------------------------------------------------------------
    def run_plan(self, plan, *, carry=None, state=None):
        """Execute a declarative `StagePlan` (repro.core.plan) of
        `edge_map` rounds against this session — the whole frontier-driven
        algorithm in one call, with the next frontier carried between rounds
        by the framework. Round-by-round this calls `edge_map` exactly as a
        hand-rolled driver loop would, so per-round stats and per-phase cost
        reports are bit-identical (the five `graph.algorithms` drivers are
        such plans). `carry` seeds the first frontier; `state` seeds user
        slots. Returns a `PlanResult`.
        """
        from ..core.plan import execute_plan  # local: avoids import cycle
        return execute_plan(self, plan, carry=carry, state=state)

    def reset_report(self) -> SessionReport:
        out, self._report = self._report, SessionReport(self.og.P)
        self.stats = []
        return out


def session_for(og, **defaults) -> GraphSession:
    """The graph's cached default session (tree machinery shared by direct
    `dist_edge_map` calls; does not record rounds)."""
    sess = getattr(og, "_default_session", None)
    if sess is None or sess.og is not og:
        sess = GraphSession(og, defaults)
        og._default_session = sess
    return sess
