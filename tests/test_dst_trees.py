"""Full-frontier rounds charge their write-back on the ingest-time
destination trees (`GraphSession.charge_full_writeback`).

The reference is the derivation every round used before: `np.unique` over
the active edges' (destination, machine) pairs, a CSR over them and a fresh
`TreeCharger` per round. Reports must match it bit for bit, and the
session's `dst_tree_reuses` counter must engage only on rounds whose active
edges are all the graph's edges."""
import numpy as np
import pytest

from repro.core.cost import CostAccumulator, assert_cost_parity, assert_session_parity
from repro.graph import (
    DistVertexSubset,
    Graph,
    GraphSession,
    TreeCharger,
    barabasi_albert,
    bfs,
    ingest,
    pagerank,
)
from repro.graph.session import VALUE_WORDS

_GRAPHS = {}


def _graph(strategy):
    """A tiny skewed graph through either ingest branch: `direct` (edges at
    the source's home) or `tdorch` (orchestrated placement, deep trees)."""
    if strategy not in _GRAPHS:
        g = barabasi_albert(150, attach=3, seed=4)
        kw = {"C": 2} if strategy == "tdorch" else {}
        _GRAPHS[strategy] = ingest(g, P=8, seed=1, strategy=strategy, **kw)
    return _GRAPHS[strategy]


def _unique_writeback(cost, og, d, em, dedup):
    """The per-round write-back charge over the active edges' destinations
    `d` and machines `em`, derived from scratch."""
    upair = np.unique(d * np.int64(og.P) + em)
    uv = (upair // og.P).astype(np.int64)
    um = (upair % og.P).astype(np.int64)
    if dedup:
        indptr = np.zeros(og.n + 1, dtype=np.int64)
        np.add.at(indptr, uv + 1, 1)
        np.cumsum(indptr, out=indptr)
        charger = TreeCharger(og.vertex_home, indptr, um, og.C)
        h = charger.charge(cost, np.unique(uv), VALUE_WORDS, upward=True)
        cost.tick(max(h, 1))
    else:
        cost.send(um, og.vertex_home[uv], VALUE_WORDS)
        cost.tick(1)


def _unique_full_writeback(self, cost, dedup):
    og = self.og
    _unique_writeback(cost, og, og.graph.dst, og.edge_machine, dedup)


def _rounds(og, k, **kw):
    """k full-frontier `add` rounds through one fresh session."""
    sess = GraphSession(og)
    vals = np.linspace(0.5, 2.0, og.n)
    wb = lambda vs, agg: np.ones(vs.size, dtype=bool)
    reports = []
    for _ in range(k):
        _, st = sess.edge_map(DistVertexSubset.full(og.n),
                              lambda s, d, w: vals[s], wb, "add",
                              force_mode="dense", **kw)
        reports.append(st.report)
    return sess, reports


@pytest.mark.parametrize("replicate", [False, True],
                         ids=["plain", "replicated"])
@pytest.mark.parametrize("keep_all", [False, True],
                         ids=["no_filter", "filter_keeps_all"])
@pytest.mark.parametrize("dedup", [True, False], ids=["dedup", "no_dedup"])
@pytest.mark.parametrize("strategy", ["direct", "tdorch"])
def test_full_frontier_report_matches_unique_derivation(
        monkeypatch, strategy, dedup, keep_all, replicate):
    og = _graph(strategy)
    kw = {"dedup": dedup, "replicate": replicate}
    if keep_all:
        kw["filter_dst"] = lambda d: np.ones(d.size, dtype=bool)
    sess, got = _rounds(og, 3, **kw)
    assert sess.dst_tree_reuses == 3
    with monkeypatch.context() as mp:
        mp.setattr(GraphSession, "charge_full_writeback",
                   _unique_full_writeback)
        ref_sess, want = _rounds(og, 3, **kw)
    assert ref_sess.dst_tree_reuses == 0
    for a, b in zip(got, want):
        assert_cost_parity(a, b)
    names = {ph.name for r in got for ph in r.phases}
    assert ("replica_refresh" in names) == replicate


def test_pagerank_counts_every_round():
    og = _graph("tdorch")
    sess = GraphSession(og)
    _, info = pagerank(og, tol=0.0, max_iter=5, session=sess)
    assert info.rounds == 5
    assert sess.dst_tree_reuses == 5


def test_bfs_on_a_path_never_reads_the_trees():
    n = 60
    a = np.arange(n - 1)
    g = Graph(n, np.concatenate([a, a + 1]), np.concatenate([a + 1, a]))
    og = ingest(g, P=4, seed=0)
    sess = GraphSession(og)
    dist, info = bfs(og, source=0, session=sess)
    assert info.rounds > 10 and dist[n - 1] == n - 1
    assert sess.dst_tree_reuses == 0
    assert sess.full_gather_reuses == 0


def test_partial_dense_frontier_keeps_the_per_round_derivation():
    og = _graph("tdorch")
    g = og.graph
    U = DistVertexSubset(og.n, indices=np.arange(0, og.n, 2))
    vals = np.arange(og.n, dtype=np.float64)
    sess = GraphSession(og)
    _, st = sess.edge_map(U, lambda s, d, w: vals[s],
                          lambda vs, agg: np.ones(vs.size, dtype=bool),
                          "min", force_mode="dense")
    assert 0 < st.active_edges < g.m
    assert sess.dst_tree_reuses == 0

    eids = np.flatnonzero(U.mask[g.src])
    d = g.dst[eids]
    cost = CostAccumulator(og.P)
    cost.begin("edgemap_dense")
    sess.src_charger.direct_broadcast(cost, U.indices, VALUE_WORDS)
    cost.tick(1)
    cost.work(og.edge_machine[eids], 1.0)
    _unique_writeback(cost, og, d, og.edge_machine[eids], True)
    cost.work(og.vertex_home[np.unique(d)], 1.0)
    cost.end()
    assert_cost_parity(st.report, cost.totals())


def test_run_plan_pagerank_matches_hand_rolled_rounds():
    og = _graph("direct")
    n, k, alpha = og.n, 4, 0.85
    plan_sess = GraphSession(og)
    pr_plan, _ = pagerank(og, alpha=alpha, tol=0.0, max_iter=k,
                          session=plan_sess)

    sess = GraphSession(og)
    deg = og.out_degree().astype(np.float64)
    pr = np.full(n, 1.0 / n)
    for _ in range(k):
        contrib = np.divide(pr, deg, out=np.zeros(n), where=deg > 0)
        nxt = np.full(n, (1 - alpha) / n + alpha * pr[deg == 0].sum() / n)

        def wb(vs, agg, nxt=nxt):
            nxt[vs] += alpha * agg
            return np.ones(vs.size, dtype=bool)

        sess.edge_map(DistVertexSubset.full(n),
                      lambda s, d, w, c=contrib: c[s], wb, "add",
                      force_mode="dense")
        pr = nxt

    np.testing.assert_array_equal(pr_plan, pr)
    assert_session_parity(plan_sess.report, sess.report)
    assert plan_sess.dst_tree_reuses == sess.dst_tree_reuses == k
