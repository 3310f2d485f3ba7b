"""Backend-parity contract (core/backend.py): for every engine, the jitted
jax backend must produce values matching the numpy oracle within float32
tolerance, while per-phase words/rounds/work match EXACTLY — the cost model
never notices which backend computed the numbers.

Matrix: all four engines x arity-1/ragged batches x replication on/off x
merge ops, plus the session-level surfaces (hash table, graph) and the
fallback/caching machinery (untraceable lambdas, device-cache invalidation).
"""
import numpy as np
import pytest

from repro.core import (DataStore, Orchestrator, TaskBatch,
                        assert_cost_parity, make_backend)

ENGINES = ["tdorch", "pull", "push", "sort"]
RTOL, ATOL = 2e-4, 1e-5  # float32 pipeline vs float64 oracle

# one shared jax backend per test module: jit caches stay warm across cases
JAX = make_backend("jax")
BACKENDS = {"numpy": make_backend("numpy"), "jax": JAX}


def _muladd(contexts, in_vals):
    mul = contexts[:, 1:2]
    add = contexts[:, 2:3]
    return {"update": in_vals * mul + add, "result": in_vals}


def _masked_sum(contexts, vals, mask):
    flat = vals.reshape(vals.shape[0], -1) if vals.ndim == 3 else vals
    # update width must equal the store's value_width (3)
    return {"update": flat[:, :3] + contexts[:, :1], "result": flat}


def _make_store(P=4, K=60, w=3, seed=0):
    rng = np.random.default_rng(seed)
    store = DataStore.create(K, P, value_width=w, chunk_words=w)
    store.write_rows(np.arange(K), rng.standard_normal((K, w)))
    return store


def _arity1_batches(K, n=72, P=4, stages=3, seed=1):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(stages):
        keys = rng.integers(0, K, n)
        is_read = rng.random(n) < 0.5
        ctx = np.concatenate([is_read[:, None].astype(float),
                              rng.standard_normal((n, 2))], axis=1)
        wk = np.where(is_read, np.int64(-1), keys)
        out.append(TaskBatch(contexts=ctx, read_keys=keys, write_keys=wk,
                             origin=TaskBatch.even_origins(n, P)))
    return out


def _ragged_batches(K, n=48, P=4, stages=2, seed=2):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(stages):
        groups = [rng.integers(0, K, rng.integers(0, 4)).tolist()
                  for _ in range(n)]
        ctx = rng.standard_normal((n, 2))
        wk = np.array([g[0] if g else -1 for g in groups], dtype=np.int64)
        out.append(TaskBatch.from_ragged(ctx, groups,
                                         TaskBatch.even_origins(n, P),
                                         write_keys=wk))
    return out


def _run(backend, engine, batches, f, merge, replication=None, seed=0):
    store = _make_store(seed=seed)
    sess = Orchestrator(store, engine=engine, backend=backend,
                        replication=replication)
    results = []
    for tasks in batches:
        res = sess.run_stage(tasks, f, write_back=merge, return_results=True)
        results.append(res)
    return store, results


def _assert_parity(store_np, res_np, store_jx, res_jx):
    assert np.allclose(store_np.values, store_jx.values, rtol=RTOL, atol=ATOL)
    for a, b in zip(res_np, res_jx):
        assert_cost_parity(a.report, b.report)
        assert np.array_equal(a.exec_site, b.exec_site)
        assert a.refcount == b.refcount
        if a.results is not None:
            assert np.allclose(np.asarray(a.results, dtype=np.float64),
                               np.asarray(b.results, dtype=np.float64),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("merge", ["write", "add", "min"])
@pytest.mark.parametrize("replicated", [False, True],
                         ids=["rep_off", "rep_on"])
def test_arity1_parity(engine, merge, replicated):
    rep = ({"num_hot": 8, "refresh": 2, "min_count": 1.0}
           if replicated else None)
    batches = _arity1_batches(K=60)
    s_np, r_np = _run("numpy", engine, batches, _muladd, merge, rep)
    s_jx, r_jx = _run(JAX, engine, batches, _muladd, merge, rep)
    _assert_parity(s_np, r_np, s_jx, r_jx)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("replicated", [False, True],
                         ids=["rep_off", "rep_on"])
def test_ragged_parity(engine, replicated):
    rep = ({"num_hot": 8, "refresh": 2, "min_count": 1.0}
           if replicated else None)
    batches = _ragged_batches(K=60)
    s_np, r_np = _run("numpy", engine, batches, _masked_sum, "add", rep)
    s_jx, r_jx = _run(JAX, engine, batches, _masked_sum, "add", rep)
    _assert_parity(s_np, r_np, s_jx, r_jx)


def test_hashtable_multiget_parity():
    from repro.kvstore import DistributedHashTable

    rng = np.random.default_rng(5)
    groups = [rng.integers(0, 100, rng.integers(0, 5)).tolist()
              for _ in range(50)]
    out = {}
    for backend in ["numpy", BACKENDS["jax"]]:
        ht = DistributedHashTable(100, 4, value_width=4, seed=3)
        ht.bulk_load(np.arange(100),
                     np.random.default_rng(7).standard_normal((100, 4)))
        out[getattr(backend, "name", backend)] = ht.multi_get(
            groups, engine="tdorch", backend=backend)
    a, b = out["numpy"], out["jax"]
    assert np.allclose(a.values, b.values, rtol=RTOL, atol=ATOL)
    assert np.array_equal(a.mask, b.mask)
    assert a.refcount == b.refcount
    assert_cost_parity(a.report, b.report)


def test_graph_parity_pagerank_cc():
    from repro.graph import generators
    from repro.graph.algorithms import cc, pagerank
    from repro.graph.partition import ingest

    g = generators.barabasi_albert(600, 4, seed=1)
    og = ingest(g, P=4)
    for alg, kw in [(pagerank, dict(max_iter=6, tol=0.0)), (cc, {})]:
        vals_np, info_np = alg(og, **kw)
        vals_jx, info_jx = alg(og, backend=JAX, **kw)
        assert np.allclose(np.asarray(vals_np, dtype=float),
                           np.asarray(vals_jx, dtype=float),
                           rtol=1e-3, atol=1e-6)
        assert info_np.rounds == info_jx.rounds
        for a, b in zip(info_np.stats, info_jx.stats):
            assert a.mode == b.mode
            assert a.active_edges == b.active_edges
            assert_cost_parity(a.report, b.report)


def test_graph_routing_cache_repeated_rounds():
    """PageRank's dense rounds re-reduce one edge set: the jax backend's
    cached routing (sorted segment-sum combine) must agree with the
    oracle on every round, including the cache-miss first round."""
    from repro.graph import generators
    from repro.graph.algorithms import pagerank
    from repro.graph.partition import ingest

    g = generators.barabasi_albert(5000, 4, seed=3)  # big enough to engage
    og = ingest(g, P=4)
    pr_np, _ = pagerank(og, max_iter=4, tol=0.0)
    pr_jx, _ = pagerank(og, max_iter=4, tol=0.0, backend="jax")
    assert np.allclose(pr_np, pr_jx, rtol=1e-3, atol=1e-7)


def test_graph_routing_cache_accurate_at_scale():
    """The cached-routing combine sums each destination's edges on their
    own: its float32 error stays relative to the segment, so PageRank on
    2^16 vertices stays within 1e-5 (L1) of the float64 oracle. (A
    difference of prefix sums over the whole edge list was off by ~1e-3
    here, ~5e-3 at 2^18.)"""
    from repro.graph import generators
    from repro.graph.algorithms import pagerank
    from repro.graph.partition import ingest

    og = ingest(generators.barabasi_albert(1 << 16, 8, seed=5), P=8)
    pr_np, _ = pagerank(og, max_iter=10, tol=0.0, account=False)
    pr_jx, _ = pagerank(og, max_iter=10, tol=0.0, account=False,
                        backend="jax")
    assert np.abs(pr_np - pr_jx).sum() < 1e-5


def test_untraceable_lambda_falls_back():
    """A lambda that cannot be traced (np.asarray on its inputs) must be
    routed to the oracle path — same values, same costs, no crash."""

    def hostile(contexts, in_vals):
        v = np.asarray(in_vals)  # TracerArrayConversionError under trace
        return {"update": v * 2.0, "result": v}

    batches = _arity1_batches(K=60, stages=2, seed=9)
    s_np, r_np = _run("numpy", "pull", batches, hostile, "add")
    s_jx, r_jx = _run(JAX, "pull", batches, hostile, "add")
    assert np.array_equal(s_np.values, s_jx.values)  # oracle path: exact
    for a, b in zip(r_np, r_jx):
        assert_cost_parity(a.report, b.report)
    assert id(hostile) in JAX._host_lambdas


@pytest.mark.parametrize("backend", ["jax", "jax_spmd"])
def test_untraceable_lambda_counted_as_host_stage(backend):
    """The oracle route stays open to a lambda JAX cannot trace — and every
    stage that takes it is counted, so a device run can assert none did."""

    def hostile(contexts, in_vals):
        v = np.asarray(in_vals)  # TracerArrayConversionError under trace
        return {"update": v + 1.0, "result": v}

    bk = make_backend(backend)
    batches = _arity1_batches(K=60, P=1, stages=2, seed=21)
    store = _make_store(P=1, seed=21)
    sess = Orchestrator(store, engine="pull", backend=bk)
    for tasks in batches:
        sess.run_stage(tasks, hostile, write_back="add", return_results=True)
    assert id(hostile) in bk._host_lambdas
    assert bk.host_stages == len(batches)


@pytest.mark.parametrize("backend", ["jax", "jax_spmd"])
def test_kernel_error_propagates(backend, monkeypatch):
    """A kernel that fails for any other reason (a compile error, a runtime
    error) must raise — not send the stage to the host in silence."""
    from repro.core import jaxexec, shardexec

    def broken(*args, **kwargs):
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(jaxexec, "_kernel_combine", broken)
    monkeypatch.setattr(shardexec, "_segment_combine", broken)

    def fresh(contexts, in_vals):  # new function object: traced afresh
        return {"update": in_vals * 2.0, "result": in_vals}

    bk = make_backend(backend)
    sess = Orchestrator(_make_store(P=1, seed=22), engine="pull", backend=bk)
    tasks = _arity1_batches(K=60, P=1, stages=1, seed=22)[0]
    with pytest.raises(RuntimeError, match="kernel refused"):
        sess.run_stage(tasks, fresh, write_back="add", return_results=True)
    assert id(fresh) not in bk._host_lambdas
    assert bk.host_stages == 0


def test_device_cache_tracks_store_version():
    """Out-of-band store mutations (write_rows between stages) must be seen
    by the jax backend's device-resident cache."""
    store = _make_store(seed=11)
    sess = Orchestrator(store, engine="pull", backend=JAX)
    batches = _arity1_batches(K=60, stages=2, seed=12)
    sess.run_stage(batches[0], _muladd, write_back="write",
                   return_results=True)
    # overwrite every value out-of-band; the next stage must read fresh rows
    store.write_rows(np.arange(store.num_keys),
                     np.full((store.num_keys, store.value_width), 7.0))
    res = sess.run_stage(batches[1], _muladd, write_back="write",
                         return_results=True)
    got = np.asarray(res.results, dtype=np.float64)
    has = batches[1].read_keys >= 0
    assert np.allclose(got[has], 7.0, rtol=RTOL, atol=ATOL)


def test_float64_dtype_requires_x64():
    import jax

    if jax.config.jax_enable_x64:  # pragma: no cover - env-dependent
        pytest.skip("x64 enabled in this environment")
    from repro.core import JaxBackend

    with pytest.raises(ValueError, match="x64"):
        JaxBackend(dtype="float64")


def test_sort_engine_routing_permutation_identical():
    """The sort engine's phase-2 permutation is cost-bearing: both backends
    must produce the identical stable order (exec_site equality pins it)."""
    batches = _arity1_batches(K=60, stages=1, seed=13)
    _, r_np = _run("numpy", "sort", batches, _muladd, "write")
    _, r_jx = _run(JAX, "sort", batches, _muladd, "write")
    assert np.array_equal(r_np[0].exec_site, r_jx[0].exec_site)
