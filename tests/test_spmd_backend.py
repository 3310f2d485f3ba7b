"""Sharded-parity contract for the mesh-sharded SPMD backend
(core/backend.py `SpmdBackend` + core/shardexec.py): for every engine the
`jax_spmd` backend must run the four phases genuinely sharded — one mesh
device per machine, each holding only its homed chunks — while producing
values matching the numpy oracle within float tolerance and per-phase
words/rounds matching EXACTLY.

The suite scales itself to the visible device count: under plain tier-1
(one CPU device) everything runs on a 1-shard mesh; the CI `spmd` job
re-runs it with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
where the collectives actually cross shards. The Zipf load-balance
assertion (the ROADMAP's "sharding" axis as a number) only runs with >= 8
devices.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.core import (DataStore, Orchestrator, TaskBatch,
                        assert_cost_parity, make_backend, shardexec)
from repro.core.backend import _bucket_rows

NDEV = len(jax.devices())
P = min(4, NDEV)
ENGINES = ["tdorch", "pull", "push", "sort"]
RTOL, ATOL = 2e-4, 1e-5  # float32 sharded pipeline vs float64 oracle

# one shared mesh backend per test module: compiled stage programs stay
# warm across cases (cache key = lambda + shape signature)
SPMD = make_backend("jax_spmd")


def _muladd(contexts, in_vals):
    mul = contexts[:, 1:2]
    add = contexts[:, 2:3]
    return {"update": in_vals * mul + add, "result": in_vals}


def _masked_sum(contexts, vals, mask):
    flat = vals.reshape(vals.shape[0], -1) if vals.ndim == 3 else vals
    return {"update": flat[:, :3] + contexts[:, :1], "result": flat}


def _make_store(P=P, K=60, w=3, seed=0):
    rng = np.random.default_rng(seed)
    store = DataStore.create(K, P, value_width=w, chunk_words=w)
    store.write_rows(np.arange(K), rng.standard_normal((K, w)))
    return store


def _arity1_batches(K, n=72, stages=3, seed=1):
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


def _ragged_batches(K, n=48, stages=2, seed=2):
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
    results = [sess.run_stage(t, f, write_back=merge, return_results=True)
               for t in batches]
    return store, results, sess


def _assert_parity(store_np, res_np, store_sx, res_sx):
    assert np.allclose(store_np.values, store_sx.values, rtol=RTOL, atol=ATOL)
    for a, b in zip(res_np, res_sx):
        assert_cost_parity(a.report, b.report)
        assert np.array_equal(a.exec_site, b.exec_site)
        assert a.refcount == b.refcount
        if a.results is not None:
            n = np.asarray(a.results).shape[0]
            assert np.allclose(
                np.asarray(a.results, dtype=np.float64).reshape(n, -1),
                np.asarray(b.results, dtype=np.float64).reshape(n, -1),
                rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("merge", ["write", "add", "min"])
@pytest.mark.parametrize("replicated", [False, True],
                         ids=["rep_off", "rep_on"])
def test_arity1_parity(engine, merge, replicated):
    rep = ({"num_hot": 8, "refresh": 2, "min_count": 1.0}
           if replicated else None)
    batches = _arity1_batches(K=60)
    s_np, r_np, _ = _run("numpy", engine, batches, _muladd, merge, rep)
    s_sx, r_sx, _ = _run(SPMD, engine, batches, _muladd, merge, rep)
    _assert_parity(s_np, r_np, s_sx, r_sx)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("replicated", [False, True],
                         ids=["rep_off", "rep_on"])
def test_ragged_parity(engine, replicated):
    rep = ({"num_hot": 8, "refresh": 2, "min_count": 1.0}
           if replicated else None)
    batches = _ragged_batches(K=60)
    s_np, r_np, _ = _run("numpy", engine, batches, _masked_sum, "add", rep)
    s_sx, r_sx, _ = _run(SPMD, engine, batches, _masked_sum, "add", rep)
    _assert_parity(s_np, r_np, s_sx, r_sx)


def test_values_match_single_device_jax():
    """The tentpole's value contract: jax_spmd vs the single-device jax
    backend, directly (not just both-vs-oracle)."""
    jx = make_backend("jax")
    batches = _arity1_batches(K=60, stages=3, seed=21)
    s_jx, r_jx, _ = _run(jx, "tdorch", batches, _muladd, "add")
    s_sx, r_sx, _ = _run(SPMD, "tdorch", batches, _muladd, "add")
    assert np.allclose(s_jx.values, s_sx.values, rtol=RTOL, atol=ATOL)
    for a, b in zip(r_jx, r_sx):
        assert_cost_parity(a.report, b.report)


def test_shard_layout_geometry():
    """Each chunk appears exactly once, on its home shard, and the inverse
    maps agree."""
    store = _make_store(K=37, seed=5)
    lay = store.shard_layout()
    assert np.array_equal(lay.owner, store.home)
    assert lay.counts.sum() == store.num_keys
    assert lay.slab_rows == int(lay.counts.max())
    live = lay.slab_keys < store.num_keys
    keys = lay.slab_keys[live]
    assert np.array_equal(np.sort(keys), np.arange(store.num_keys))
    # inverse: slab_keys[home[k], local_slot[k]] == k
    back = lay.slab_keys[store.home, lay.local_slot]
    assert np.array_equal(back, np.arange(store.num_keys))
    assert store.shard_layout() is lay  # cached


def test_shard_stats_measure_real_placement():
    """The measured per-shard task counts must equal the cost model's
    execution-site placement — the execution really shards the way the
    model assumes."""
    SPMD.reset_stats()
    batches = _arity1_batches(K=60, stages=1, seed=7)
    _, res, _ = _run(SPMD, "push", batches, _muladd, "add")
    stats = SPMD.stage_stats[-1]
    want = np.bincount(res[0].exec_site, minlength=P)
    assert np.array_equal(stats.tasks, want)
    assert stats.tasks.sum() == batches[0].n
    assert stats.work_ratio() >= 1.0


def test_replica_slab_serves_hot_reads():
    """With replication on, the sharded fetch must serve hot chunks from
    the shard-local replica slab (measured), and the slab must stay fresh
    across write-backs (values keep matching the oracle)."""
    rep = {"num_hot": 8, "refresh": 1, "min_count": 1.0}
    batches = _arity1_batches(K=12, n=64, stages=4, seed=11)
    SPMD.reset_stats()
    s_np, r_np, _ = _run("numpy", "tdorch", batches, _muladd, "write", rep)
    s_sx, r_sx, _ = _run(SPMD, "tdorch", batches, _muladd, "write", rep)
    _assert_parity(s_np, r_np, s_sx, r_sx)
    measured = sum(int(st.replica_local.sum()) for st in SPMD.stage_stats)
    assert measured > 0  # later stages read hot chunks shard-locally


def test_session_report_per_machine():
    batches = _arity1_batches(K=60, stages=2, seed=13)
    _, _, sess = _run("numpy", "tdorch", batches, _muladd, "add")
    pm = sess.report.per_machine()
    assert pm["work"].shape == (P,)
    assert pm["h_relation"].shape == (P,)
    assert pm["max_work"] == pytest.approx(float(pm["work"].max()))
    assert pm["work_ratio"] >= 1.0
    if pm["max_h"] > 0:  # P=1 meshes move no words (self-sends are free)
        assert pm["h_ratio"] >= 1.0
    assert pm["work_ratio"] == pytest.approx(
        float(pm["work"].max()) / float(pm["work"].mean()))
    # bit-identical across backends, like every cost quantity
    _, _, sess_sx = _run(SPMD, "tdorch", batches, _muladd, "add")
    pm_sx = sess_sx.report.per_machine()
    assert np.array_equal(pm["work"], pm_sx["work"])
    assert np.array_equal(pm["h_relation"], pm_sx["h_relation"])


def test_one_dimensional_results_keep_their_shape():
    """A lambda returning a 1-D (n,) result must come back with exactly
    the oracle's shape — not lifted to (n, 1) by the sharded transport."""

    def scalar_result(contexts, in_vals):
        return {"result": in_vals[:, 0] * 2.0}

    batches = _arity1_batches(K=60, stages=1, seed=17)
    _, r_np, _ = _run("numpy", "pull", batches, scalar_result, "add")
    _, r_sx, _ = _run(SPMD, "pull", batches, scalar_result, "add")
    assert np.asarray(r_np[0].results).shape \
        == np.asarray(r_sx[0].results).shape
    assert np.allclose(np.asarray(r_np[0].results, dtype=np.float64),
                       np.asarray(r_sx[0].results, dtype=np.float64),
                       rtol=RTOL, atol=ATOL)
    assert_cost_parity(r_np[0].report, r_sx[0].report)


def test_one_dimensional_contexts_reach_the_lambda_unchanged():
    """TaskBatch supports 1-D contexts; the sharded transport must hand
    them to the lambda with their rank intact (and actually run sharded —
    not quietly fall back to the oracle)."""

    def scale(ctx, vals):
        assert ctx.ndim == 1  # static under trace: fails loudly if lifted
        return {"result": vals * ctx[:, None]}

    ctx = np.random.default_rng(29).standard_normal(40)
    keys = np.random.default_rng(30).integers(0, 60, 40)

    def mk():
        return TaskBatch(contexts=ctx.copy(), read_keys=keys,
                         origin=TaskBatch.even_origins(40, P))

    a = _run("numpy", "pull", [mk()], scale, "add")
    b = _run(SPMD, "pull", [mk()], scale, "add")
    _assert_parity(a[0], a[1], b[0], b[1])
    assert id(scale) not in SPMD._host_lambdas  # really ran on the mesh


def test_untraceable_lambda_falls_back():
    def hostile(contexts, in_vals):
        v = np.asarray(in_vals)  # TracerArrayConversionError under trace
        return {"update": v * 2.0, "result": v}

    batches = _arity1_batches(K=60, stages=2, seed=9)
    s_np, r_np, _ = _run("numpy", "pull", batches, hostile, "add")
    s_sx, r_sx, _ = _run(SPMD, "pull", batches, hostile, "add")
    assert np.array_equal(s_np.values, s_sx.values)  # oracle path: exact
    for a, b in zip(r_np, r_sx):
        assert_cost_parity(a.report, b.report)
    assert id(hostile) in SPMD._host_lambdas


def test_slab_cache_tracks_store_version():
    """Out-of-band mutations between stages must invalidate the sharded
    residency, exactly like the single-device device-values cache."""
    store = _make_store(seed=11)
    sess = Orchestrator(store, engine="pull", backend=SPMD)
    batches = _arity1_batches(K=60, stages=2, seed=12)
    sess.run_stage(batches[0], _muladd, write_back="write",
                   return_results=True)
    store.write_rows(np.arange(store.num_keys),
                     np.full((store.num_keys, store.value_width), 7.0))
    res = sess.run_stage(batches[1], _muladd, write_back="write",
                         return_results=True)
    got = np.asarray(res.results, dtype=np.float64)
    has = batches[1].read_keys >= 0
    assert np.allclose(got[has], 7.0, rtol=RTOL, atol=ATOL)


def test_run_plan_front_door():
    """StagePlan chains (the kv run_chain path) run through the sharded
    backend with batch-identical hops."""
    from repro.kvstore import DistributedHashTable

    rng = np.random.default_rng(23)
    keys = rng.integers(0, 80, (24, 3))
    op = rng.standard_normal((24, 2))
    out = {}
    for backend in ["numpy", SPMD]:
        ht = DistributedHashTable(80, P, value_width=4, seed=3)
        ht.bulk_load(np.arange(80),
                     np.random.default_rng(7).standard_normal((80, 4)))
        out[getattr(backend, "name", backend)] = ht.run_chain(
            keys, op, engine="tdorch", backend=backend)
    a, b = out["numpy"], out["jax_spmd"]
    assert a.hops == b.hops
    assert np.array_equal(a.keys, b.keys)
    assert np.allclose(np.nan_to_num(a.values), np.nan_to_num(b.values),
                       rtol=RTOL, atol=ATOL)
    for ra, rb in zip(a.reports, b.reports):
        assert_cost_parity(ra, rb)


def test_graph_front_door():
    from repro.graph import generators
    from repro.graph.algorithms import pagerank
    from repro.graph.partition import ingest

    g = generators.barabasi_albert(400, 4, seed=1)
    og = ingest(g, P=P)
    v_np, i_np = pagerank(og, max_iter=5, tol=0.0)
    v_sx, i_sx = pagerank(og, backend=SPMD, max_iter=5, tol=0.0)
    assert np.allclose(np.asarray(v_np, float), np.asarray(v_sx, float),
                       rtol=1e-3, atol=1e-6)
    assert i_np.rounds == i_sx.rounds
    for a, b in zip(i_np.stats, i_sx.stats):
        assert_cost_parity(a.report, b.report)


def test_too_few_devices_fails_loudly():
    """Requesting more machines than devices must raise with the CPU
    recipe in the message — at session construction, before any stage."""
    store = DataStore.create(16, NDEV + 1, value_width=2, chunk_words=2)
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count"):
        Orchestrator(store, engine="tdorch", backend="jax_spmd")
    with pytest.raises(RuntimeError, match="one device per machine"):
        make_backend("jax_spmd").validate_machines(NDEV + 1)


@pytest.mark.skipif(NDEV < 8, reason="needs an 8-device mesh "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_zipf_skew_balance_with_replication():
    """The acceptance claim: on the Zipf alpha=1.2 skewed workload with
    replication on, the tdorch session's per-machine max/mean work ratio
    stays <= 1.5 on an 8-shard mesh — the paper's O(W/P) balance as an
    asserted number."""
    from repro.kvstore import make_ycsb_stream

    P8 = 8
    nkeys = 4096
    store = DataStore.create(nkeys, P8, value_width=8, chunk_words=8)
    sess = Orchestrator(store, engine="tdorch", backend=SPMD,
                        replication={"num_hot": 64, "refresh": 2,
                                     "decay": 0.5, "min_count": 8.0})
    origin = TaskBatch.even_origins(500 * P8, P8)
    for keys, is_read, operand in make_ycsb_stream(
            "C", 500, P8, nkeys, gamma=1.2, seed=17, stages=6):
        ctx = np.concatenate(
            [is_read[:, None].astype(np.float64), operand], axis=1)
        wk = np.where(is_read, np.int64(-1), keys)
        tasks = TaskBatch(contexts=ctx, read_keys=keys, write_keys=wk,
                          origin=origin)
        sess.run_stage(tasks, _muladd, write_back="write")
    pm = sess.report.per_machine()
    assert pm["work_ratio"] <= 1.5, pm["work_ratio"]


# ---------------------------------------------------------------------------
# a warm stage is sized by the batch, not by the table
# ---------------------------------------------------------------------------
def _on_four_devices(name: str) -> None:
    """Run the check `name` (a function of this module) on a four-shard
    mesh: here when the process has four devices, else in a child process
    with four virtual CPU devices."""
    if NDEV >= 4:
        globals()[name]()
        return
    here = pathlib.Path(__file__).resolve().parent
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {str(here)!r})
        import test_spmd_backend
        test_spmd_backend.{name}()
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=str(here.parent / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "OK" in out.stdout, out.stderr[-3000:]


def _recorded_programs():
    """Wrap `shardexec.build_stage_program` so every stage call records its
    non-slab operand shapes and the compiled program's temp bytes (lowered
    before the call: the call donates the slabs)."""
    calls = []
    build = shardexec.build_stage_program

    def recording_build(*args, **kw):
        prog = build(*args, **kw)

        def run(*a):
            mem = prog.lower(*a).compile().memory_analysis()
            calls.append(([np.shape(x) for x in a[1:]],
                          None if mem is None else mem.temp_size_in_bytes))
            return prog(*a)
        return run

    shardexec.build_stage_program = recording_build
    return calls, lambda: setattr(shardexec, "build_stage_program", build)


def _warm_stage(K, batches, f, merge):
    """Run `batches` over a fresh K-key store; the last stage's counter
    deltas and its program's recorded operand shapes and temp bytes."""
    bk = make_backend("jax_spmd")
    store = DataStore.create(K, P, value_width=3, chunk_words=3)
    store.write_rows(np.arange(K),
                     np.random.default_rng(K).standard_normal((K, 3)))
    sess = Orchestrator(store, engine="tdorch", backend=bk)
    calls, restore = _recorded_programs()
    try:
        for t in batches[:-1]:
            sess.run_stage(t, f, write_back=merge, return_results=True)
        before = (bk.transfer_bytes, bk.exchange_bytes, bk.exchange_rows,
                  bk.host_syncs)
        sess.run_stage(batches[-1], f, write_back=merge, return_results=True)
    finally:
        restore()
    after = (bk.transfer_bytes, bk.exchange_bytes, bk.exchange_rows,
             bk.host_syncs)
    return [b - a for a, b in zip(before, after)], calls[-1]


def check_warm_stage_independent_of_table():
    """The same batches over 2^12 and 2^16 keys: the warm stage moves the
    same bytes to and from the host and through the all-to-alls, takes
    the same non-slab operands and needs the same temp memory."""
    for f, merge, make in ((_muladd, "write", _arity1_batches),
                           (_masked_sum, "add", _ragged_batches)):
        small = _warm_stage(1 << 12, make(K=1 << 12), f, merge)
        big = _warm_stage(1 << 16, make(K=1 << 12), f, merge)
        assert small[0] == big[0], (merge, small[0], big[0])
        assert small[1][0] == big[1][0], merge
        if small[1][1] is not None:  # the backend reports temp bytes
            assert small[1][1] == big[1][1], merge


def check_stage_stats_match_key_histogram():
    """Every `ShardStageStats` field equals its derivation from the batch,
    `owned_demand` from the K-bin histogram of requested keys."""
    K = 60
    bk = make_backend("jax_spmd")
    store = _make_store(K=K, seed=31)
    owner = store.shard_layout().owner
    sess = Orchestrator(store, engine="tdorch", backend=bk)
    for t in _arity1_batches(K=K, stages=3, seed=32):
        res = sess.run_stage(t, _muladd, write_back="write",
                             return_results=True)
        st = bk.stage_stats[-1]
        site = res.exec_site
        active = t.read_keys >= 0
        hist = np.bincount(t.read_keys[active], minlength=K)
        demand = np.array([hist[owner == m].sum() for m in range(P)])
        w = t.write_keys >= 0
        sent = [np.unique(t.write_keys[w & (site == m)]) for m in range(P)]
        recv = np.zeros(P, dtype=np.int64)
        for keys in sent:
            recv += np.bincount(owner[keys], minlength=P)
        want = dict(
            tasks=np.bincount(site, minlength=P),
            pairs=np.bincount(site[active], minlength=P),
            fetch_sent=np.bincount(site[active], minlength=P),
            fetch_recv=demand, replica_local=np.zeros(P, np.int64),
            writers=np.bincount(site[w], minlength=P),
            combine_sent=np.array([k.size for k in sent]),
            combine_recv=recv, owned_demand=demand)
        for field, value in want.items():
            assert np.array_equal(getattr(st, field), value), field


def check_write_ties_resolve_as_oracle():
    """Writers of one key on different shards with tied priorities: the
    lowest global row wins, as in the numpy oracle."""
    K, n = 8, 96
    rng = np.random.default_rng(41)
    keys = rng.integers(0, 3, n)
    ctx = np.concatenate([np.zeros((n, 1)), rng.standard_normal((n, 2))],
                         axis=1)
    prio = rng.integers(0, 2, n)

    def run(backend):
        store = _make_store(K=K, seed=42)
        sess = Orchestrator(store, engine="pull", backend=backend)
        t = TaskBatch(contexts=ctx, read_keys=keys, write_keys=keys,
                      priority=prio, origin=TaskBatch.even_origins(n, P))
        res = sess.run_stage(t, _muladd, write_back="write")
        return store.values, res.exec_site

    want, _ = run("numpy")
    got, site = run(make_backend("jax_spmd"))
    assert np.allclose(got, want, rtol=RTOL, atol=ATOL)
    # the test means something: a key's winning priority is held by tasks
    # on at least two shards
    k0 = keys == 0
    top = k0 & (prio == prio[k0].min())
    assert np.unique(site[top]).size >= 2


def check_blockwise_upload_equals_whole_table(block_rows=5):
    """Slabs staged a few rows of every shard at a time hold exactly what
    staging the whole table at once would."""
    store = _make_store(K=61, seed=43)
    lay = store.shard_layout()
    bk = make_backend("jax_spmd")
    keep, shardexec.UPLOAD_ROWS = shardexec.UPLOAD_ROWS, block_rows
    try:
        slabs = shardexec._slabs_for(store, shardexec.get_mesh(P),
                                     np.float32, bk)
    finally:
        shardexec.UPLOAD_ROWS = keep
    live = lay.slab_keys < store.num_keys
    rows, words = shardexec.slab_shape(lay.slab_rows, 3)
    whole = np.zeros((P, rows, words), dtype=np.float32)
    whole[:, :lay.slab_rows][live, :3] = store.values[lay.slab_keys[live]]
    assert lay.slab_rows % block_rows  # the last block is a short one
    np.testing.assert_array_equal(np.asarray(slabs), whole)
    assert bk.transfer_bytes == P * lay.slab_rows * words * 4


def check_exchange_counters_by_hand():
    """A YCSB-like stage's all-to-all bytes (padded buffers) and live rows,
    counted by hand; the stage syncs with the host three times, as before
    (statistics, results, the written rows)."""
    K, W, n = 64, 3, 40
    bk = make_backend("jax_spmd")
    store = _make_store(K=K, w=W, seed=44)
    sess = Orchestrator(store, engine="tdorch", backend=bk)
    batches = _arity1_batches(K=K, n=n, stages=2, seed=45)
    sess.run_stage(batches[0], _muladd, write_back="write")
    before = (bk.exchange_bytes, bk.exchange_rows, bk.host_syncs)
    t = batches[1]
    res = sess.run_stage(t, _muladd, write_back="write", return_results=True)
    site = res.exec_site
    T = _bucket_rows(int(np.bincount(site, minlength=P).max()))
    fetch = P * T * (4 + W * 4)  # slab rows asked for, rows sent back
    write = P * T * (W * 4 + 3 * 4)  # rows, slab row, order, row id
    w = t.write_keys >= 0
    combined = sum(np.unique(t.write_keys[w & (site == m)]).size
                   for m in range(P))
    pairs = int((t.read_keys >= 0).sum())
    assert bk.exchange_bytes - before[0] == P * (fetch + write)
    assert bk.exchange_rows - before[1] == 2 * pairs + combined
    assert bk.host_syncs - before[2] == 3


def check_chip_smoke_mesh_phase():
    """`chip_smoke.py --chips 4`'s mesh phase, cut to a 1,000-record table
    and small batches, passes against its own reference: it reads every
    device shard of the padded slabs."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    import chip_smoke

    chip_smoke.YCSB_OPS = 1 << 10
    chip_smoke.spmd_phase(seed=5, chips=P, keys_per_chip=250)


@pytest.mark.parametrize("check", [
    "check_warm_stage_independent_of_table",
    "check_stage_stats_match_key_histogram",
    "check_write_ties_resolve_as_oracle",
    "check_blockwise_upload_equals_whole_table",
    "check_exchange_counters_by_hand",
    "check_chip_smoke_mesh_phase",
])
def test_on_a_four_shard_mesh(check):
    _on_four_devices(check)


@pytest.mark.parametrize("writes", [True, False], ids=["writes", "reads"])
def test_resident_slab_is_the_stage_output(writes):
    """The stage donates the resident slabs and its output takes their
    place, whether or not it wrote; the resident values are the host
    values at their slab rows."""
    store = _make_store(seed=46)
    bk = make_backend("jax_spmd")
    sess = Orchestrator(store, engine="tdorch", backend=bk)
    b0, b1 = _arity1_batches(K=60, stages=2, seed=47)
    sess.run_stage(b0, _muladd, write_back="write")
    before = bk._slabs(store)
    if not writes:
        b1 = TaskBatch(contexts=b1.contexts, read_keys=b1.read_keys,
                       write_keys=np.full(b1.n, -1), origin=b1.origin)
    sess.run_stage(b1, _muladd, write_back="write", return_results=True)
    after = bk._slabs(store)
    assert after is not before and before.is_deleted()
    assert store.__dict__["_spmd_values"]["float32"][0] == store.version
    lay = store.shard_layout()
    view = bk.resident(store)
    assert view.shape == (P, lay.slab_rows, store.value_width)
    got = np.asarray(view)[lay.owner, lay.local_slot]
    np.testing.assert_allclose(got, store.values, rtol=RTOL, atol=ATOL)
    # read as the benchmark's check reads it: columns of every row, rows
    cols, keys = np.array([0, 2]), np.array([5, 17, 42])
    by_col = np.asarray(view[:, :, cols])[lay.owner, lay.local_slot]
    by_row = np.asarray(view[lay.owner[keys], lay.local_slot[keys]])
    np.testing.assert_array_equal(by_col, got[:, cols])
    np.testing.assert_array_equal(by_row, got[keys])


@pytest.mark.parametrize("idx", [
    (slice(None), slice(None), np.array([0, 2])),
    (np.array([0, P - 1, P - 1]), np.array([3, 0, 14])),
    (Ellipsis, 1),
    (P - 1,),
    (slice(None), -1),
    (slice(None), slice(None, None, -3), slice(1, None)),
    (np.array([True, False, True, False])[:P], slice(2, 9)),
    (0, np.array([-1, -2]), Ellipsis),
], ids=["cols", "rows", "ellipsis", "shard", "last_row", "neg_step",
        "mask", "neg_rows"])
def test_slab_view_reads_as_the_logical_array(idx):
    """The resident view indexes like the (P, slab_rows, w) array it holds,
    never reaching the tile padding of the device slabs; the rest of the
    array's surface is the padded device array's."""
    store = _make_store(K=61, seed=48)
    bk = make_backend("jax_spmd")
    Orchestrator(store, engine="tdorch", backend=bk).run_stage(
        _arity1_batches(K=61, stages=1, seed=49)[0], _muladd,
        write_back="write")
    view = bk.resident(store)
    whole = np.asarray(view)
    assert whole.shape == view.shape
    idx = idx[0] if len(idx) == 1 else idx
    np.testing.assert_array_equal(np.asarray(view[idx]), whole[idx])
    slabs = bk._slabs(store)
    assert view.sharding == slabs.sharding and view.nbytes == slabs.nbytes
    assert len(view.addressable_shards) == P
