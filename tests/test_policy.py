"""Differential policy-conformance suite for `engine="auto"` (core/policy.py).

The adaptive loop is pinned four ways:

  * **oracle bound** — over a grid of synthetic workloads (uniform, Zipf
    α∈{0.8, 1.2, 1.5}, adversarial single-hot-chunk, graph frontiers), all
    four fixed engines run exhaustively on identical streams and auto's
    realized total words (decision latency INCLUDED) must stay within 1.1x
    of the per-stage argmin oracle;
  * **estimator honesty** — predicted vs. realized words agree exactly for
    conforming lambdas (the estimators' documented tolerance is zero when
    update/result widths match and no stealing intervenes), per-phase via
    `assert_cost_parity`, not just scalars — for the engine auto chose,
    for each engine on its own, and for each edge-map mode;
  * **bit-reproducibility** — decision sequences are identical across
    repeat runs and across numeric backends (numpy vs. jax), because every
    estimator input is parity-pinned;
  * **estimator drift** — a pinned table of `estimate_cost` outputs on a
    fixed fixture per engine: changing an engine's charging rules without
    consciously updating its estimator fails loudly here.

Graph-side: `GraphSession(engine="auto")`'s sparse/dense mode policy must
pick the argmin of its own (exact) estimates, adapt hub vs. frontier
rounds, and record decisions like the kv side.
"""
import numpy as np
import pytest

from repro.core import (DataStore, Orchestrator, TaskBatch,
                        assert_cost_parity, orchestration)
from repro.core.cost import POLICY_PHASE, REPLICA_REFRESH_PHASE, StageReport
from repro.core.policy import (PhaseCostEstimate, PolicyConfig, StageLayout,
                               StagePolicy, make_policy_config)
from repro.kvstore.ycsb import zipf_keys_stationary

P, K, W = 8, 64, 4
ENGINES = ["tdorch", "pull", "push", "sort"]
ORACLE_FACTOR = 1.1
NON_ENGINE_PHASES = (POLICY_PHASE, REPLICA_REFRESH_PHASE)


def _store():
    store = DataStore.create(K, P, value_width=W, chunk_words=W)
    rng = np.random.default_rng(99)
    store.write_rows(np.arange(K), rng.standard_normal((K, W)))
    return store


def _muladd(ctx, vals):
    return {"update": vals * ctx[:, :1] + ctx[:, 1:2]}


def _batch(keys, origin, seed):
    rng = np.random.default_rng(seed)
    n = keys.size
    return TaskBatch(read_keys=keys, write_keys=keys.copy(),
                     contexts=rng.standard_normal((n, 2)),
                     origin=origin)


# ---------------------------------------------------------------------------
# workload grid: each entry yields a deterministic list of TaskBatches
# ---------------------------------------------------------------------------
def _uniform_stream(stages=4, n=320, seed=0):
    rng = np.random.default_rng(seed)
    return [_batch(rng.integers(0, K, n), rng.integers(0, P, n), seed + i)
            for i in range(stages)]


def _zipf_stream(alpha, stages=4, n=320, seed=1):
    rng = np.random.default_rng(seed)
    perm = rng.permutation(K)
    return [_batch(zipf_keys_stationary(n, K, alpha, rng, perm),
                   rng.integers(0, P, n), seed + i)
            for i in range(stages)]


def _hot_chunk_stream(stages=4, n=320, seed=2):
    """Adversarial: every task reads/writes the SAME chunk — the worst case
    for pull (home swamped with B-word replies) and push (home swamped with
    contexts and all the work)."""
    rng = np.random.default_rng(seed)
    return [_batch(np.zeros(n, dtype=np.int64), rng.integers(0, P, n),
                   seed + i)
            for i in range(stages)]


def _frontier_stream(stages=5, seed=3):
    """Graph-frontier shape over the key space: a synthetic adjacency on the
    K chunks, one edge-relaxation task per (frontier vertex, neighbor) —
    read the source chunk, write the destination chunk, originate at the
    source's home. Frontier sizes swing across rounds, which is exactly the
    regime a per-stage policy must track."""
    rng = np.random.default_rng(seed)
    adj = [rng.choice(K, size=rng.integers(8, 17), replace=False)
           for _ in range(K)]
    store = _store()
    frontier = np.arange(6, dtype=np.int64)
    out = []
    for i in range(stages):
        src = np.repeat(frontier, [len(adj[int(v)]) for v in frontier])
        dst = np.concatenate([adj[int(v)] for v in frontier]) \
            if frontier.size else np.empty(0, dtype=np.int64)
        n = src.size
        b = TaskBatch(read_keys=src.astype(np.int64),
                      write_keys=dst.astype(np.int64),
                      contexts=rng.standard_normal((n, 2)),
                      origin=store.home[src])
        out.append(b)
        frontier = np.unique(dst)
    return out


WORKLOADS = {
    "uniform": _uniform_stream,
    "zipf_0.8": lambda: _zipf_stream(0.8),
    "zipf_1.2": lambda: _zipf_stream(1.2),
    "zipf_1.5": lambda: _zipf_stream(1.5),
    "hot_chunk": _hot_chunk_stream,
    "frontier": _frontier_stream,
}
REPLICATION = {"num_hot": 8, "refresh": 2, "min_count": 1.0}


def _run(engine, batches, *, backend=None, replication=None):
    sess = Orchestrator(_store(), engine=engine, backend=backend,
                        replication=replication)
    for b in batches:
        sess.run_stage(b, _muladd, write_back="add")
    return sess


def _engine_words(stage: StageReport) -> float:
    """A stage's words excluding policy/refresh phases — the apples-to-apples
    quantity across engines (refresh is engine-independent, policy is
    auto-only)."""
    return sum(float(ph.sent.sum()) for ph in stage.phases
               if ph.name not in NON_ENGINE_PHASES)


# ---------------------------------------------------------------------------
# the 1.1x per-stage argmin-oracle gate (decision latency included)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("replication", [None, REPLICATION],
                         ids=["plain", "replicated"])
@pytest.mark.parametrize("workload", sorted(WORKLOADS))
def test_auto_within_oracle_bound(workload, replication):
    batches = WORKLOADS[workload]()
    fixed = {e: _run(e, batches, replication=replication) for e in ENGINES}
    auto = _run("auto", batches, replication=replication)
    oracle = 0.0
    for i in range(len(batches)):
        oracle += min(_engine_words(fixed[e].report.stages[i])
                      for e in ENGINES)
    realized = sum(_engine_words(st) for st in auto.report.stages)
    assert realized <= ORACLE_FACTOR * oracle + 1e-9, (
        f"{workload}: auto realized {realized} words vs per-stage argmin "
        f"oracle {oracle} — exceeds the {ORACLE_FACTOR}x bound")
    # The decision tax is a separate, fixed O(P) toll per stage — never a
    # function of batch size, so it amortizes as stages grow. Pin its exact
    # closed form: active non-coordinator machines ship a sketch_words
    # demand sketch to machine 0, which broadcasts a decision_words verdict
    # (self-sends free on both legs).
    cfg = PolicyConfig()
    assert len(auto.report.policy_decisions) == len(batches)
    for b, d in zip(batches, auto.report.policy_decisions):
        active = np.unique(b.origin)
        expect = cfg.sketch_words * np.count_nonzero(active != 0) \
            + cfg.decision_words * (P - 1)
        assert d.policy_words == expect
        assert sorted(d.predicted) == sorted(ENGINES)
    assert auto.report.policy_words == \
        sum(d.policy_words for d in auto.report.policy_decisions)


# ---------------------------------------------------------------------------
# estimator honesty: predicted == realized for conforming lambdas,
# per-phase, and auto's stage == the chosen engine's stage bit-for-bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("workload", sorted(WORKLOADS))
def test_predicted_matches_realized_exactly(workload):
    batches = WORKLOADS[workload]()
    auto = _run("auto", batches, replication=REPLICATION)
    assert len(auto.report.policy_decisions) == len(batches)
    for d, stage in zip(auto.report.policy_decisions, auto.report.stages):
        assert d.predicted_words == pytest.approx(d.realized_words, abs=0), (
            f"stage {d.stage_index}: predicted {d.predicted_words} != "
            f"realized {d.realized_words} for chosen engine {d.choice}")
        # full per-phase pin, not just the scalar
        realized = StageReport(stage.P, [
            ph for ph in stage.phases if ph.name not in NON_ENGINE_PHASES])
        assert_cost_parity(d.estimate.report, realized)


@pytest.mark.parametrize("workload", ["zipf_1.2", "hot_chunk"])
def test_auto_stage_bitidentical_to_chosen_engine(workload):
    """Auto's stage report minus the policy phase must equal the chosen
    fixed engine's stage report exactly — same replica evolution (the
    demand feed totals are engine-independent), same charges. Store values
    are engine-independent by the simulation-fidelity contract, so they
    must be bit-equal too."""
    batches = WORKLOADS[workload]()
    auto = _run("auto", batches, replication=REPLICATION)
    fixed = {e: _run(e, batches, replication=REPLICATION) for e in ENGINES}
    for i, d in enumerate(auto.report.policy_decisions):
        assert_cost_parity(auto.report.stages[i],
                           fixed[d.choice].report.stages[i],
                           ignore=(POLICY_PHASE,))
    for e in ENGINES:
        assert np.array_equal(auto.store.values, fixed[e].store.values)


# ---------------------------------------------------------------------------
# bit-reproducibility: across repeat runs and across backends
# ---------------------------------------------------------------------------
def _decision_trace(sess):
    return [(d.stage_index, d.choice, d.incumbent, d.switched,
             tuple(sorted(d.predicted.items())), d.predicted_words,
             d.realized_words, d.policy_words)
            for d in sess.report.policy_decisions]


@pytest.mark.parametrize("workload", sorted(WORKLOADS))
def test_decisions_reproducible_across_runs(workload):
    batches = WORKLOADS[workload]()
    a = _run("auto", batches, replication=REPLICATION)
    b = _run("auto", batches, replication=REPLICATION)
    assert _decision_trace(a) == _decision_trace(b)


@pytest.mark.parametrize("workload", ["zipf_1.2", "hot_chunk"])
def test_decisions_reproducible_across_backends(workload):
    """The decision inputs (the layout, the engines' charge routines,
    parity-pinned argsort_stable) are backend-independent, so the decision
    stream — and with it the whole per-phase cost report — must be
    bit-identical between the numpy oracle and the jitted jax backend."""
    batches = WORKLOADS[workload]()
    a = _run("auto", batches, replication=REPLICATION)
    b = _run("auto", batches, backend="jax", replication=REPLICATION)
    assert _decision_trace(a) == _decision_trace(b)
    for sa, sb in zip(a.report.stages, b.report.stages):
        assert_cost_parity(sa, sb)


# ---------------------------------------------------------------------------
# hysteresis: the incumbent survives noise, loses to a decisive challenger
# ---------------------------------------------------------------------------
def _est(name, words):
    from repro.core.cost import CostAccumulator
    cost = CostAccumulator(2)
    cost.begin("synthetic")
    cost.send(np.array([0]), np.array([1]), float(words))
    cost.tick()
    cost.end()
    return PhaseCostEstimate(name, cost.totals())


def test_hysteresis_prevents_thrash():
    policy = StagePolicy(PolicyConfig(candidates=("a", "b"), hysteresis=0.05))
    d1 = policy.choose({"a": _est("a", 100), "b": _est("b", 110)})
    assert d1.choice == "a" and d1.incumbent is None and not d1.switched
    # challenger 2% better: inside the 5% band — no switch
    d2 = policy.choose({"a": _est("a", 102), "b": _est("b", 100)})
    assert d2.choice == "a" and not d2.switched
    # challenger decisively better: switch
    d3 = policy.choose({"a": _est("a", 100), "b": _est("b", 50)})
    assert d3.choice == "b" and d3.switched and d3.incumbent == "a"
    # ties break by candidate order, deterministically
    fresh = StagePolicy(PolicyConfig(candidates=("a", "b")))
    assert fresh.choose({"a": _est("a", 7), "b": _est("b", 7)}).choice == "a"


def test_hysteresis_keeps_oracle_bound():
    """The default hysteresis band must be narrow enough that holding the
    incumbent can never break the 1.1x oracle gate: worst case the
    incumbent is kept at best/(1 - h)."""
    h = PolicyConfig().hysteresis
    assert 1.0 / (1.0 - h) <= ORACLE_FACTOR


def test_policy_config_coercion():
    assert make_policy_config(None) == PolicyConfig()
    cfg = make_policy_config({"candidates": ["pull", "push"],
                              "hysteresis": 0.2})
    assert cfg.candidates == ("pull", "push") and cfg.hysteresis == 0.2
    assert make_policy_config(cfg) is cfg
    with pytest.raises(TypeError):
        make_policy_config("tdorch")
    with pytest.raises(ValueError):
        StagePolicy().choose({})
    with pytest.raises(ValueError):
        _est("x", 1).objective_value("nonsense")


def test_restricted_candidates_front_door():
    """Policy knobs ride engine_opts: a session may restrict the candidate
    set (e.g. forest-free deployments) and the decision honors it."""
    batches = WORKLOADS["zipf_1.2"]()
    sess = Orchestrator(_store(), engine="auto",
                        policy={"candidates": ("pull", "sort")})
    sess.run_stage(batches[0], _muladd, write_back="add")
    d = sess.report.policy_decisions[0]
    assert sorted(d.predicted) == ["pull", "sort"]
    assert d.choice in ("pull", "sort")
    with pytest.raises(ValueError, match="not estimable"):
        Orchestrator(_store(), engine="auto",
                     policy={"candidates": ("pull", "warp")})


# ---------------------------------------------------------------------------
# estimator drift: pinned estimate_cost outputs on a fixed fixture
# ---------------------------------------------------------------------------
_ENGINE_FILES = {
    "tdorch": "src/repro/core/engine.py",
    "pull": "src/repro/core/baselines.py",
    "push": "src/repro/core/baselines.py",
    "sort": "src/repro/core/baselines.py",
}

# Pinned on the fixture below (P=8, K=64, W=4, 320 Zipf-1.2 tasks, no
# replicas). Regenerate with:
#   PYTHONPATH=src python -c "import test_policy as t; t._print_drift_table()"
# from tests/ — and when a number moves, make sure the matching engine's
# charging rules in _ENGINE_FILES changed on purpose, estimator included.
_DRIFT_TABLE = {
    "tdorch": {"total_words": 1930.0, "rounds": 7, "max_comm": 330.0},
    "pull": {"total_words": 2256.0, "rounds": 3, "max_comm": 571.0},
    "push": {"total_words": 1144.0, "rounds": 1, "max_comm": 372.0},
    "sort": {"total_words": 2720.2534966642115, "rounds": 5,
             "max_comm": 388.2534966642116},
}


def _drift_fixture():
    store = _store()
    batches = _zipf_stream(1.2, stages=1, n=320, seed=41)
    tasks = batches[0]
    layout = StageLayout.capture(tasks, store)
    return store, tasks, layout


def _print_drift_table():  # regeneration helper, not a test
    from repro.core.registry import make_engine
    store, tasks, layout = _drift_fixture()
    for name in ENGINES:
        est = make_engine(name, P).estimate_cost(layout)
        print(f'    "{name}": {{"total_words": {est.total_words!r}, '
              f'"rounds": {est.rounds!r}, "max_comm": {est.max_comm!r}}},')


@pytest.mark.parametrize("engine", ENGINES)
def test_estimator_drift_pinned(engine):
    from repro.core.registry import make_engine
    store, tasks, layout = _drift_fixture()
    est = make_engine(engine, P).estimate_cost(layout)
    got = {"total_words": est.total_words, "rounds": est.rounds,
           "max_comm": est.max_comm}
    want = _DRIFT_TABLE[engine]
    assert got == pytest.approx(want, rel=1e-12), (
        f"estimate_cost({engine}) drifted from the pinned table:\n"
        f"  pinned: {want}\n  got:    {got}\n"
        f"If you changed {_ENGINE_FILES[engine]}'s charging rules, its "
        f"estimate_cost must change WITH run_stage (they share the same "
        f"word-counting) — then refresh _DRIFT_TABLE in tests/test_policy.py "
        f"via _print_drift_table().")


# ---------------------------------------------------------------------------
# every engine's estimate is its own run_stage bill, per phase
# ---------------------------------------------------------------------------
def _conforming(ctx, vals, mask):
    """Updates and results `W` words wide, as `StageLayout` assumes."""
    if vals.ndim == 3:
        vals = (vals * mask[..., None]).sum(axis=1)
    return {"update": vals * ctx[:, :1] + ctx[:, 1:2], "result": vals}


def _multiget_batch(n=240, seed=17):
    """Ragged reads of arity 0–4 over Zipf keys; writes to the primary
    key, to another key, or none; tasks that only write."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(K)
    lists = [zipf_keys_stationary(int(rng.integers(0, 5)), K, 1.1, rng, perm)
             for _ in range(n)]
    wk = np.array([k[0] if len(k) and i % 3 else
                   (int(rng.integers(0, K)) if i % 4 else -1)
                   for i, k in enumerate(lists)], dtype=np.int64)
    return TaskBatch.from_ragged(rng.standard_normal((n, 2)), lists,
                                 rng.integers(0, P, n), write_keys=wk)


def _replica_set(tasks, seed=5):
    """The batch's eight most requested keys, each held by a random half
    of the machines."""
    from repro.core.replication import ReplicaSet
    rng = np.random.default_rng(seed)
    counts = np.bincount(tasks.read_indices, minlength=K)
    hot = np.argsort(-counts, kind="stable")[:8].astype(np.int64)
    lookup = np.full(K, -1, dtype=np.int64)
    lookup[hot] = np.arange(hot.size)
    return ReplicaSet(hot_ids=hot, lookup=lookup,
                      holders=rng.random((hot.size, P)) < 0.5)


@pytest.mark.parametrize("results", [False, True], ids=["noresults",
                                                        "results"])
@pytest.mark.parametrize("replicated", [False, True],
                         ids=["plain", "replicated"])
@pytest.mark.parametrize("batch", ["arity1", "multiget"])
@pytest.mark.parametrize("engine", ENGINES)
def test_estimate_equals_run_stage(engine, batch, replicated, results):
    """`estimate_cost(layout)` is the engine's own `run_stage` bill, phase
    by phase, for a lambda that meets the layout's width assumptions."""
    from repro.core.registry import make_engine
    tasks = (_zipf_stream(1.2, stages=1, n=320, seed=41)[0]
             if batch == "arity1" else _multiget_batch())
    replicas = _replica_set(tasks) if replicated else None
    store = _store()
    layout = StageLayout.capture(tasks, store, replicas=replicas,
                                 return_results=results)
    est = make_engine(engine, P).estimate_cost(layout)
    res = make_engine(engine, P).run_stage(tasks, store, _conforming,
                                           return_results=results,
                                           replicas=replicas)
    assert_cost_parity(est.report, res.report)


@pytest.mark.parametrize("dedup", [True, False], ids=["dedup", "nodedup"])
@pytest.mark.parametrize("replicated", [False, True],
                         ids=["plain", "replicated"])
@pytest.mark.parametrize("mode", ["sparse", "dense"])
def test_edge_map_estimate_equals_realized_propagation(monkeypatch, mode,
                                                       replicated, dedup):
    """A mode's estimate equals the source-value propagation that a round
    forced into that mode charges: its phase as it stands when the
    `edgemap.propagate` span closes."""
    import contextlib
    import copy

    from repro.core.cost import CostAccumulator
    from repro.graph import (DistVertexSubset, GraphSession, barabasi_albert,
                             ingest)
    from repro.graph import distedgemap as dem

    og = ingest(barabasi_albert(400, 4, seed=7), P=P)
    sess = GraphSession(og)
    U = DistVertexSubset(og.n, indices=np.arange(0, og.n, 3))
    acc = np.zeros(og.n)

    def f(s, d, w):
        return w

    def write_back(vs, agg):
        acc[vs] += agg
        return np.ones(vs.size, dtype=bool)

    kw = dict(session=sess, merge_value="add", force_mode=mode, dedup=dedup)
    replicas = None
    if replicated:
        rep = sess.ensure_replicator({"num_hot": 16, "refresh": 1000,
                                      "min_count": 1.0})
        dem.dist_edge_map(og, U, f, write_back, **kw)  # feeds the demand
        rep.refresh()  # elected now: the measured round does not re-elect
        replicas = rep.replicas
        assert replicas.hot_ids.size
    est = dem._estimate_mode_costs(og, sess, U.indices, replicas,
                                   dedup)[mode].report

    made, seen = [], []

    class Recording(CostAccumulator):
        def __init__(self, num_machines):
            super().__init__(num_machines)
            made.append(self)

    real_span = dem.span

    @contextlib.contextmanager
    def spy(name, **args):
        with real_span(name, **args):
            yield
        if name == "edgemap.propagate":
            seen.append(copy.deepcopy(made[-1]._open))

    monkeypatch.setattr(dem, "CostAccumulator", Recording)
    monkeypatch.setattr(dem, "span", spy)
    _, stats = dem.dist_edge_map(og, U, f, write_back, **kw)
    assert stats.mode == mode and len(seen) == 1
    assert_cost_parity(est, StageReport(P, seen))


# ---------------------------------------------------------------------------
# every front door accepts engine="auto"
# ---------------------------------------------------------------------------
def test_front_door_orchestration_shim():
    batches = WORKLOADS["uniform"]()
    res = orchestration(batches[0], _muladd, _store(), engine="auto")
    assert res.decision is not None and res.decision.choice in ENGINES
    assert any(ph.name == POLICY_PHASE for ph in res.report.phases)


def test_front_door_hashtable_and_plan():
    from repro.kvstore import DistributedHashTable
    rng = np.random.default_rng(5)
    ht = DistributedHashTable(K, P, value_width=W)
    ht.bulk_load(np.arange(K), rng.standard_normal((K, W)))
    keys = rng.integers(0, K, 200)
    res = ht.execute_batch(keys, np.zeros(200, dtype=bool),
                           rng.random((200, 2)), engine="auto")
    assert any(ph.name == POLICY_PHASE for ph in res.report.phases)
    # run_plan re-decides per emitted round: one decision per hop
    sess = ht.session(engine="auto")
    n0 = len(sess.report.policy_decisions)
    chain = ht.run_chain(rng.integers(0, K, (24, 3)),
                         rng.standard_normal((24, 2)), engine="auto")
    decs = sess.report.policy_decisions[n0:]
    assert len(decs) == chain.hops
    assert [d.stage_index for d in decs] == \
        list(range(n0, n0 + chain.hops))


def test_front_door_serve():
    from repro.kvstore import DistributedHashTable
    rng = np.random.default_rng(6)
    ht = DistributedHashTable(K, P, value_width=W)
    ht.bulk_load(np.arange(K), rng.standard_normal((K, W)))
    fe = ht.serve(engine="auto", mode="sync",
                  config={"max_batch": 16, "min_window": 10.0,
                          "max_window": 10.0})
    handles = [fe.get(int(k)) for k in rng.integers(0, K, 32)]
    fe.flush()
    fe.close()
    assert all(h.done() for h in handles)
    decs = [d for s in fe.sessions for d in s.report.policy_decisions]
    assert len(decs) >= 1
    assert sum(s.report.policy_words for s in fe.sessions) > 0


def test_front_door_paramserve():
    from repro.paramserve import EmbeddingStore, MoERouter
    router = MoERouter(6, 5, 7, P, top_k=2, seed=2)
    router.init_weights(3)
    x, ti, g = router.zipf_routing(24, alpha=1.2, seed=4)
    out = router.decode_step(x, ti, g, engine="auto")
    assert any(ph.name == POLICY_PHASE for ph in out.report.phases)
    es = EmbeddingStore(30, 3, P, seed=5)
    es.init_table(6)
    look = es.lookup(np.arange(10), engine="auto")
    assert any(ph.name == POLICY_PHASE for ph in look.report.phases)


# ---------------------------------------------------------------------------
# graph side: the sparse/dense mode policy
# ---------------------------------------------------------------------------
def test_graph_mode_policy_adapts_and_is_consistent():
    from repro.graph import GraphSession, bfs, ingest, star_graph
    og = ingest(star_graph(4096), P=32)
    sess = GraphSession(og, engine="auto")
    bfs(og, 0, session=sess, force_mode=None)
    decs = sess.report.policy_decisions
    assert len(decs) == sess.num_rounds and len(decs) >= 2
    # hub round rides the tree; the flat frontier round broadcasts directly
    assert decs[0].choice == "sparse" and decs[1].choice == "dense"
    cfg = sess.mode_policy.config
    for d in decs:
        assert d.kind == "edge_map_mode"
        # internal consistency: the choice is the argmin of its own
        # estimates, unless hysteresis explicitly held the incumbent
        best = min(("sparse", "dense"), key=d.predicted.__getitem__)
        if d.choice != best:
            assert d.incumbent == d.choice
            assert d.predicted[best] >= \
                d.predicted[d.choice] * (1.0 - cfg.hysteresis)
    assert sess.report.policy_words > 0


def test_graph_mode_decisions_reproducible():
    from repro.graph import GraphSession, barabasi_albert, ingest, pagerank
    og = ingest(barabasi_albert(600, 4, seed=3), P=8)
    traces = []
    for _ in range(2):
        sess = GraphSession(og, engine="auto")
        pagerank(og, session=sess, force_mode=None, max_iter=4, tol=0.0)
        traces.append([(d.stage_index, d.choice,
                        tuple(sorted(d.predicted.items())))
                       for d in sess.report.policy_decisions])
    assert traces[0] == traces[1] and len(traces[0]) == 4


def test_graph_mode_policy_tracks_fixed_modes():
    """Words tie between modes under T1 dedup, so the policy's win shows on
    the BSP axis: per round, auto (minus the fixed O(P) decision toll,
    gated on its own) must stay within the 1.1x envelope of the better
    fixed mode's bsp_time at the policy's own round-latency."""
    from repro.graph import GraphSession, bfs, ingest, star_graph

    def _bsp(stage, L):
        engine = StageReport(stage.P, [ph for ph in stage.phases
                                       if ph.name != POLICY_PHASE])
        return engine.bsp_time(t=0.0, L=L)

    og = ingest(star_graph(4096), P=32)
    auto = GraphSession(og, engine="auto")
    bfs(og, 0, session=auto, force_mode=None)
    L = auto.mode_policy.config.round_latency
    fixed = {}
    for fm in ("sparse", "dense"):
        s = GraphSession(og)
        bfs(og, 0, session=s, force_mode=fm)
        fixed[fm] = s.report.stages
    oracle = sum(min(_bsp(fixed[fm][i], L) for fm in fixed)
                 for i in range(auto.num_rounds))
    realized = sum(_bsp(st, L) for st in auto.report.stages)
    assert realized <= ORACLE_FACTOR * oracle + 1e-9
    # the per-round oracle can only lower-bound any fixed mode
    for fm in fixed:
        assert sum(_bsp(st, L) for st in fixed[fm]) >= oracle - 1e-9
    assert auto.report.policy_words > 0
