"""Host spans and transfer counters of the orchestrator (core/spans.py).

Under a `jax.profiler` trace the program's ``tdorch.*`` spans come back
from the trace file nested as `spans.SPANS` documents; with or without the
trace the results, the cost reports and the counters are the same; and
`transfer_bytes` counts exactly the bytes each host↔device copy moves.
"""
import glob
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import assert_cost_parity, make_backend
from repro.core.spans import PREFIX, SCOPES, SPANS, span
from repro.graph import GraphSession, barabasi_albert, ingest, pagerank
from repro.kvstore import DistributedHashTable

ROOT = pathlib.Path(__file__).resolve().parents[1]
K, P, W, N = 4096, 4, 4, 64  # keys, machines, words a row, ops a batch


def _batch(seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, N)  # contended: 64 ops over 256 hot keys
    is_read = np.zeros(N, dtype=bool)
    is_read[rng.permutation(N)[:N // 2]] = True
    operand = rng.standard_normal((N, 2))
    return keys, is_read, operand


def _table():
    ht = DistributedHashTable(K, P, value_width=W)
    ht.bulk_load(np.arange(K),
                 np.random.default_rng(0).standard_normal((K, W)))
    return ht


def _traced(fn, log_dir):
    """fn() under a profiler trace; returns (its value, the tdorch spans as
    dicts of name, start, end, line, args), sorted by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    spans = []
    for path in glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append({
                            "name": ev.name[len(PREFIX):],
                            "start": ev.start_ns,
                            "end": ev.start_ns + ev.duration_ns,
                            "line": (plane.name, line.name),
                            "args": dict(ev.stats)})
    return out, sorted(spans, key=lambda s: (s["start"], -s["end"]))


def _inside(child, parent):
    return (child["line"] == parent["line"] and child is not parent
            and parent["start"] <= child["start"]
            and child["end"] <= parent["end"])


def _children(spans, parent, name):
    return [s for s in spans if s["name"] == name and _inside(s, parent)]


def test_span_helper_names():
    assert len(set(SPANS)) == len(SPANS)
    with span("stage", stage=3):  # usable with no trace running
        pass


def test_kv_batch_spans(tmp_path):
    ht = _table()
    ht.execute_batch(*_batch(1), engine="tdorch", backend="jax")  # compiles
    _, spans = _traced(
        lambda: [ht.execute_batch(*_batch(s), engine="tdorch", backend="jax")
                 for s in (2, 3)], tmp_path)
    assert {s["name"] for s in spans} <= set(SPANS)
    batches = [s for s in spans if s["name"] == "kv.batch"]
    assert len(batches) == 2
    stage_ids = []
    for b in batches:
        assert len(_children(spans, b, "kv.make_batch")) == 1
        (st,) = _children(spans, b, "stage")
        stage_ids.append(st["args"]["stage"])
        phases = [_children(spans, st, f"phase{i}") for i in (1, 2, 3, 4)]
        assert all(len(p) == 1 for p in phases)
        starts = [p[0]["start"] for p in phases]
        assert starts == sorted(starts)
        p3, p4 = phases[2][0], phases[3][0]
        assert len(_children(spans, p3, "backend.dispatch")) == 1
        assert len(_children(spans, p3, "backend.fetch")) == 1  # results
        (wb,) = _children(spans, p4, "backend.writeback")
        assert len(_children(spans, wb, "backend.fetch")) == 1  # combined
        assert len(_children(spans, p4, "backend.dispatch")) == 1  # apply
    assert stage_ids[1] == stage_ids[0] + 1


def test_pagerank_round_spans(tmp_path):
    og = ingest(barabasi_albert(600, 4, seed=1), P=P, seed=1)
    sess = GraphSession(og, backend="jax")
    pagerank(og, tol=0.0, max_iter=2, session=sess)  # compiles
    _, spans = _traced(
        lambda: pagerank(og, tol=0.0, max_iter=2, session=sess), tmp_path)
    assert {s["name"] for s in spans} <= set(SPANS)
    rounds = [s for s in spans if s["name"] == "plan.round"]
    assert [r["args"]["round"] for r in rounds] == [0, 1]
    for r in rounds:
        assert _children(spans, r, "plan.host")  # round body and until
        (em,) = _children(spans, r, "edgemap")
        for child in ("edgemap.gather", "edgemap.propagate", "edgemap.f",
                      "edgemap.combine", "edgemap.writeback_cost",
                      "edgemap.apply"):
            assert len(_children(spans, em, child)) == 1, child


def _kv_run(log_dir=None):
    ht = _table()
    run = lambda: [ht.execute_batch(*_batch(s), engine="tdorch",  # noqa: E731
                                    backend="jax") for s in (1, 2, 3)]
    out = run() if log_dir is None else _traced(run, log_dir)[0]
    bk = ht.session("tdorch", backend="jax").backend
    return out, ht.values.copy(), bk


def _pr_run(log_dir=None):
    og = ingest(barabasi_albert(600, 4, seed=1), P=P, seed=1)
    sess = GraphSession(og, backend="jax")
    run = lambda: pagerank(og, tol=0.0, max_iter=3,  # noqa: E731
                           session=sess)
    out = run() if log_dir is None else _traced(run, log_dir)[0]
    return out, sess.backend


@pytest.mark.parametrize("workload", ["kv", "pagerank"])
def test_trace_changes_nothing(workload, tmp_path):
    if workload == "kv":
        (plain, vals_a, bk_a), (traced, vals_b, bk_b) = (
            _kv_run(), _kv_run(tmp_path))
        for a, b in zip(plain, traced):
            np.testing.assert_array_equal(a.values, b.values)
            assert_cost_parity(a.report, b.report)
        np.testing.assert_array_equal(vals_a, vals_b)
    else:
        ((pr_a, info_a), bk_a), ((pr_b, info_b), bk_b) = (
            _pr_run(), _pr_run(tmp_path))
        np.testing.assert_array_equal(pr_a, pr_b)
        assert info_a.rounds == info_b.rounds
        for a, b in zip(info_a.stats, info_b.stats):
            assert_cost_parity(a.report, b.report)
    assert bk_a.host_syncs == bk_b.host_syncs > 0
    assert bk_a.transfer_bytes == bk_b.transfer_bytes > 0


def test_transfer_bytes_by_hand():
    ht = _table()
    bk = make_backend("jax")
    sess = ht.session("tdorch", backend=bk)
    f32 = 4
    for i, seed in enumerate((1, 2)):
        keys, is_read, operand = _batch(seed)
        before = bk.transfer_bytes
        ht.execute_batch(keys, is_read, operand, engine="tdorch", backend=bk)
        B = 16  # writer rows, padded to the bucket (at least 16)
        while B < N // 2:
            B *= 2
        want = (N * 4  # read keys, int32
                + N * 3 * f32  # contexts: (is_read, multiplier, addend)
                + 3 * B * 4  # writer rows, segments, priorities
                + N * W * f32  # results fetched
                + B * 4  # written keys of the apply
                + B * W * f32)  # combined rows fetched for the mirror
        if i == 0:
            want += K * W * f32  # the table's first upload
        assert bk.transfer_bytes - before == want
    assert sess.backend is bk


def test_spmd_stats_read_is_counted():
    """On a 4-device mesh a sharded stage fetches its results and its
    per-shard statistics: two host syncs, both in transfer_bytes."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        from repro.core import DataStore, Orchestrator, TaskBatch
        from repro.core.backend import _bucket_rows
        P, K, W, n = 4, 2048, 3, 32

        def get(c, v):
            return {"result": v}

        store = DataStore.create(K, P, value_width=W, chunk_words=W)
        store.write_rows(np.arange(K), np.ones((K, W)))
        sess = Orchestrator(store, engine="tdorch", backend="jax_spmd")
        bk = sess.backend
        tasks = TaskBatch(contexts=np.ones((n, 1)),
                          read_keys=np.arange(n) % K,
                          origin=TaskBatch.even_origins(n, P))
        sess.run_stage(tasks, get, return_results=True)
        syncs, moved = bk.host_syncs, bk.transfer_bytes
        sess.run_stage(tasks, get, return_results=True)
        stats = bk.stage_stats[-1]
        assert bk.host_syncs - syncs == 2, bk.host_syncs - syncs
        T = _bucket_rows(int(stats.tasks.max()))  # task slots a shard
        fetched = P * T * W * 4 + P * len(stats) * 4  # results + stats
        # ctx (1 word), valid, writer keys with their owners and slab rows,
        # order, row ids, read keys' owners and slab rows
        uploaded = (P * T * (4 + 1 + 3 * 4 + 4 + 4 + 2 * 4)
                    + P * 4  # replica rows, 1 slot (nothing replicated)
                    + 2 * P * 4 + P * 1  # ragged-only operands, 1 slot each
                    + P * (4 + 4 + W * 4))  # replica dummies, every shard
        assert bk.transfer_bytes - moved == fetched + uploaded, (
            bk.transfer_bytes - moved, fetched + uploaded)
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert "OK" in out.stdout, out.stderr[-2000:]


def _flat_update(contexts, vals):
    return {"update": vals.reshape(vals.shape[0], -1), "result": vals}


def _program_text(fn, *args, **kw):
    return fn.lower(*args, **kw).as_text(debug_info=True)


@pytest.mark.parametrize("program", ["flat", "ragged", "apply"])
def test_named_scopes_in_stage_programs(program):
    """The device phases carry their scope names in the compiled program's
    op metadata (what HLO dumps and XProf's op views show)."""
    import jax.numpy as jnp

    from repro.core import jaxexec
    from repro.kvstore.hashtable import _muladd_lambda

    values = jnp.zeros((64, W), jnp.float32)
    ctx = jnp.ones((8, 3), jnp.float32)
    idx = jnp.arange(8, dtype=jnp.int32)
    kw = dict(f=_muladd_lambda, fwd_mask=False, merge_name="add",
              combine=True, want_update=False, want_result=True)
    if program == "flat":
        text = _program_text(jaxexec.run_stage_flat, values, idx, ctx, idx,
                             idx, idx, **kw)
        want = ("phase3_gather_lambda", "phase4_combine")
    elif program == "ragged":
        mask = jnp.ones((8, 1), bool)
        text = _program_text(jaxexec.run_stage_ragged, values, idx, idx,
                             jnp.zeros(8, jnp.int32), mask, ctx, idx, idx,
                             idx, **{**kw, "f": _flat_update})
        want = ("phase3_gather_lambda", "phase4_combine")
    else:
        text = _program_text(jaxexec.apply_rows, values, idx,
                             jnp.ones((8, W), jnp.float32), merge_name="add")
        want = ("phase4_apply",)
    for scope in want:
        assert scope in text, scope


def test_named_scopes_in_sharded_stage(monkeypatch):
    from repro.core import DataStore, Orchestrator, TaskBatch, shardexec

    texts = []
    build = shardexec.build_stage_program

    def recording_build(*args, **kw):
        prog = build(*args, **kw)

        def run(*a):
            texts.append(prog.lower(*a).as_text(debug_info=True))
            return prog(*a)
        return run

    monkeypatch.setattr(shardexec, "build_stage_program", recording_build)
    store = DataStore.create(2048, 1, value_width=W, chunk_words=W)
    sess = Orchestrator(store, engine="tdorch", backend="jax_spmd")
    n = 32
    tasks = TaskBatch(contexts=np.ones((n, 3)), read_keys=np.arange(n),
                      write_keys=np.arange(n), origin=np.zeros(n, np.int64))
    sess.run_stage(tasks, lambda c, v: {"update": v + 1, "result": v},
                   return_results=True)
    (text,) = texts
    for scope in SCOPES:  # the sharded program names every phase
        assert scope in text, scope
