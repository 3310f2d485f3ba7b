"""Dense rounds over a full frontier read the ingested edge arrays
(`GraphSession.full_edges`) and charge their edge compute from the
ingest-time edges per machine (`GraphSession.charge_full_work`).

The reference is what every such round did before: a gather of the active
edge ids (`flatnonzero` over the frontier's mask), fresh copies of the
sources, destinations and weights, and a per-edge `cost.work`. Reports,
combined values and next frontiers must match it bit for bit, and the
session's `full_gather_reuses` counter must engage only on dense rounds
whose frontier is every vertex and whose filter keeps every edge."""
import numpy as np
import pytest

from repro.core.cost import assert_cost_parity, assert_session_parity
from repro.graph import (
    DistVertexSubset,
    GraphSession,
    barabasi_albert,
    ingest,
    pagerank,
)

_GRAPHS = {}


def _graph(strategy, weighted=False):
    """A tiny skewed graph through either ingest branch, weighted or not."""
    key = (strategy, weighted)
    if key not in _GRAPHS:
        g = barabasi_albert(150, attach=3, seed=4)
        if weighted:
            g = g.with_weights(seed=2)
        kw = {"C": 2} if strategy == "tdorch" else {}
        _GRAPHS[key] = ingest(g, P=8, seed=1, strategy=strategy, **kw)
    return _GRAPHS[key]


def _gathered_edges(self):
    """The per-round gather a full dense frontier used to run."""
    g = self.og.graph
    eids = np.flatnonzero(np.ones(self.og.n, dtype=bool)[g.src])
    w = g.weights[eids] if g.weights is not None else np.ones(eids.size)
    return eids, g.src[eids], g.dst[eids], w


def _per_edge_work(self, cost, units):
    cost.work(self.og.edge_machine[np.arange(self.og.m)], units)


def _reference(mp):
    mp.setattr(GraphSession, "full_edges", property(_gathered_edges))
    mp.setattr(GraphSession, "charge_full_work", _per_edge_work)


def _rounds(og, k, merge="add", **kw):
    """k full-frontier rounds through one fresh session; returns the
    session, the reports, the combined values and the next frontiers."""
    sess = GraphSession(og)
    vals = np.linspace(0.5, 2.0, og.n)
    reports, combined, frontiers = [], [], []

    def wb(vs, agg):
        combined.append((vs.copy(), agg.copy()))
        return agg > 1.0

    for r in range(k):
        nxt, st = sess.edge_map(DistVertexSubset.full(og.n),
                                lambda s, d, w, r=r: vals[s] * w + r,
                                wb, merge, force_mode="dense", **kw)
        reports.append(st.report)
        frontiers.append(nxt.indices)
    return sess, reports, combined, frontiers


@pytest.mark.parametrize("replicate", [False, True],
                         ids=["plain", "replicated"])
@pytest.mark.parametrize("keep_all", [False, True],
                         ids=["no_filter", "filter_keeps_all"])
@pytest.mark.parametrize("fast_local", [True, False],
                         ids=["fast_local", "cas_loop"])
@pytest.mark.parametrize("dedup", [True, False], ids=["dedup", "no_dedup"])
@pytest.mark.parametrize("strategy", ["direct", "tdorch"])
def test_full_frontier_report_matches_per_edge_derivation(
        monkeypatch, strategy, dedup, fast_local, keep_all, replicate):
    og = _graph(strategy)
    kw = {"dedup": dedup, "fast_local": fast_local, "replicate": replicate}
    if keep_all:
        kw["filter_dst"] = lambda d: np.ones(d.size, dtype=bool)
    sess, got, _, _ = _rounds(og, 3, **kw)
    assert sess.full_gather_reuses == 3
    with monkeypatch.context() as mp:
        _reference(mp)
        _, want, _, _ = _rounds(og, 3, **kw)
    for a, b in zip(got, want):
        assert_cost_parity(a, b)


def test_per_edge_comm_bill_matches_per_edge_derivation(monkeypatch):
    og = _graph("tdorch")
    _, got, _, _ = _rounds(og, 2, per_edge_comm=True, fast_local=False)
    with monkeypatch.context() as mp:
        _reference(mp)
        _, want, _, _ = _rounds(og, 2, per_edge_comm=True, fast_local=False)
    for a, b in zip(got, want):
        assert_cost_parity(a, b)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("merge", ["add", "min", "max", "write"])
def test_full_frontier_values_and_frontiers_match(monkeypatch, merge,
                                                  weighted):
    og = _graph("tdorch", weighted)
    _, _, got, got_nxt = _rounds(og, 2, merge)
    with monkeypatch.context() as mp:
        _reference(mp)
        _, _, want, want_nxt = _rounds(og, 2, merge)
    for (gv, ga), (wv, wa) in zip(got, want):
        np.testing.assert_array_equal(gv, wv)
        np.testing.assert_array_equal(ga, wa)
    for a, b in zip(got_nxt, want_nxt):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_pagerank_counts_every_round_and_matches(monkeypatch, backend):
    # 4,000+ edges, so the jax backend's segment sum runs on the device path
    og = ingest(barabasi_albert(600, attach=4, seed=3), P=8, seed=2)
    sess = GraphSession(og, backend=backend)
    pr, info = pagerank(og, tol=0.0, max_iter=4, session=sess)
    assert info.rounds == 4
    assert sess.full_gather_reuses == sess.dst_tree_reuses == 4
    with monkeypatch.context() as mp:
        _reference(mp)
        ref_sess = GraphSession(og, backend=backend)
        want, _ = pagerank(og, tol=0.0, max_iter=4, session=ref_sess)
    np.testing.assert_array_equal(pr, want)
    assert_session_parity(sess.report, ref_sess.report)


def _sparse_full(og, sess):
    return sess.edge_map(DistVertexSubset.full(og.n), lambda s, d, w: w,
                         lambda vs, agg: np.ones(vs.size, dtype=bool),
                         "add", force_mode="sparse")


def _partial_dense(og, sess):
    U = DistVertexSubset(og.n, indices=np.arange(0, og.n, 2))
    return sess.edge_map(U, lambda s, d, w: w,
                         lambda vs, agg: np.ones(vs.size, dtype=bool),
                         "add", force_mode="dense")


def _filter_drops(og, sess):
    return sess.edge_map(DistVertexSubset.full(og.n), lambda s, d, w: w,
                         lambda vs, agg: np.ones(vs.size, dtype=bool),
                         "add", filter_dst=lambda d: d % 2 == 0,
                         force_mode="dense")


@pytest.mark.parametrize("round_", [_sparse_full, _partial_dense,
                                    _filter_drops],
                         ids=["sparse_full", "partial_dense", "filter_drops"])
def test_other_rounds_gather_as_before(monkeypatch, round_):
    og = _graph("tdorch")
    sess = GraphSession(og)
    nxt, st = round_(og, sess)
    assert 0 < st.active_edges
    assert sess.full_gather_reuses == 0
    with monkeypatch.context() as mp:
        _reference(mp)
        ref_sess = GraphSession(og)
        want_nxt, want = round_(og, ref_sess)
    assert_cost_parity(st.report, want.report)
    np.testing.assert_array_equal(nxt.indices, want_nxt.indices)


def test_a_callback_that_writes_its_arguments_raises():
    og = _graph("direct")
    src = og.graph.src.copy()
    sess = GraphSession(og)

    def f(s, d, w):
        s[:] = 0
        return w

    with pytest.raises(ValueError, match="read-only"):
        sess.edge_map(DistVertexSubset.full(og.n), f,
                      lambda vs, agg: np.ones(vs.size, dtype=bool), "add",
                      force_mode="dense")
    np.testing.assert_array_equal(og.graph.src, src)
    assert og.graph.src.flags.writeable
