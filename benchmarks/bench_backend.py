"""Execution-backend shootout: numpy oracle vs jitted JAX, same engines.

The tentpole claim under test: threading `backend="jax"` through an
`Orchestrator` / `GraphSession` makes the *numeric* per-stage loop (padded
gather → lambda → segment-⊗-combine → ⊙-apply, `repro.core.jaxexec`) faster
than the float64 numpy reference, while per-phase words/rounds stay
bit-identical (pinned separately by `tests/test_backend_parity.py`; here the
words_per_task metric is emitted per backend so the regression gate notices
if the backends ever diverge — the two rows of a cell must agree exactly).

Workloads:
  * YCSB-C (read-only serving) over Zipf keys through a long-lived
    `DistributedHashTable` session per backend — the production shape: the
    jitted session keeps the table device-resident across batches, and the
    fused gather+lambda is where XLA beats the numpy oracle outright.
    Compile + first upload happen in the timing warmup, as they would once
    per serving process. (Write-heavy batches — YCSB A/B — are ⊙-apply
    scatter-bound, which CPU XLA executes serially: they roughly break even
    here and are covered by the parity tests instead; on TPU the
    `repro.kernels.segment_combine` Pallas path is the remedy. The oracle
    remains the right CPU backend for write-heavy *simulation*.)
  * PageRank on a Barabási–Albert graph through `GraphSession(backend=...)`
    with the cost model off (`account=False`) — the pure execution path a
    device deployment runs, via the cached routing permutation + sorted
    segment-sum combine — and once with it on, to show the
    end-to-end simulator also benefits.

  * Skewed ragged multiget (`backend/multiget/...`): Zipf-keyed batches
    where ~10% of tasks request `amax` chunks and the rest request one —
    the worst case for the legacy `(n, max_arity, w)` padded gather, which
    materializes `amax` slots for every task. The same fused-able lambda
    (`repro.core.fused_read`) runs once with `kernel_backend="padded"` and
    once with `"fused"` (the ragged-native `kernels/stage_fused` route) on
    the jax backend; the speedup row is fused-vs-padded wall, and the
    per-variant ``words_per_task`` pins that the routing bill is identical.

Rows: ``backend/<workload>/<cell>/<backend>`` with ``wall_ms`` (+
deterministic ``words_per_task`` where the cost model runs) and one
``.../speedup`` summary row per cell: metrics ``speedup`` =
numpy wall / jax wall (>1 = jitted wins) — or padded wall / fused wall for
the multiget cells (>1 = the ragged kernel route wins).
"""
from __future__ import annotations

import numpy as np

from repro.core import DataStore, Orchestrator, TaskBatch, fused_read
from repro.graph import generators
from repro.graph.algorithms import pagerank
from repro.graph.partition import ingest
from repro.kvstore import DistributedHashTable, make_ycsb_batch

from .common import row, timeit

BACKENDS = ["numpy", "jax"]
SEED = 17


def _ycsb_cells(quick: bool):
    tpm = 4_000 if quick else 20_000  # tasks per machine
    P = 8
    nkeys = 8 * tpm * P
    stages = 3 if quick else 4
    width = 16
    for wl, gamma in [("C", 1.5), ("C", 2.0)]:
        for engine in ["tdorch", "pull"]:
            yield wl, gamma, engine, tpm, P, nkeys, stages, width


def _zipf_keys(rng, K, size, gamma):
    ranks = np.arange(1, K + 1, dtype=np.float64) ** (-gamma)
    cdf = np.cumsum(ranks)
    cdf /= cdf[-1]
    return np.searchsorted(cdf, rng.random(size)).astype(np.int64)


def _finish_scale(c, r):
    return r * c[:, :1]


def _skewed_batch(rng, n, P, K, gamma, amax):
    """~10% of tasks read `amax` Zipf-hot chunks, the rest read one; half
    the tasks write back to their first read key."""
    arity = np.where(rng.random(n) < 0.1, amax, 1).astype(np.int64)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(arity, out=indptr[1:])
    indices = _zipf_keys(rng, K, int(indptr[-1]), gamma)
    write_keys = np.where(rng.random(n) < 0.5, indices[indptr[:-1]], -1)
    return TaskBatch(contexts=rng.standard_normal((n, 2)),
                     origin=rng.integers(0, P, n).astype(np.int64),
                     write_keys=write_keys, read_indptr=indptr,
                     read_indices=indices)


def _multiget_cells(quick: bool):
    n = 4_000 if quick else 12_000  # tasks per batch
    P = 8
    K = 4 * n
    stages = 3 if quick else 4
    for gamma in (1.2, 1.5):
        for amax in (8, 64):
            yield gamma, amax, n, P, K, stages


def run(quick: bool = False):
    rows = []

    # ---------------- YCSB batches through hash-table sessions -------------
    for wl, gamma, engine, tpm, P, nkeys, stages, width in _ycsb_cells(quick):
        batches = [
            make_ycsb_batch(wl, tpm, P, nkeys, gamma=gamma, seed=SEED + s)
            for s in range(stages)
        ]
        cell = f"backend/ycsb/{wl}/zipf{gamma}/{engine}"
        wall = {}
        for backend in BACKENDS:
            ht = DistributedHashTable(nkeys, P, value_width=width)

            def call():
                for keys, is_read, operand in batches:
                    ht.execute_batch(keys, is_read, operand, engine=engine,
                                     backend=backend)

            wall[backend] = timeit(call, repeats=3, warmup=1)
            ht.session(engine, backend=backend).reset_report()
            call()
            rep = ht.session_report(engine, backend=backend)
            wpt = float(rep.sent.sum()) / (tpm * P * stages)
            rows.append(row(
                f"{cell}/{backend}", wall[backend] * 1e6,
                f"words_per_task={wpt:.3f};stages={stages}",
                seed=SEED, words_per_task=wpt,
                wall_ms=wall[backend] * 1e3))
        sp = wall["numpy"] / wall["jax"]
        rows.append(row(f"{cell}/speedup", 0.0,
                        f"{sp:.2f}x jitted vs numpy wall", seed=SEED,
                        speedup=sp))

    # ---------------- skewed ragged multiget: fused vs padded --------------
    width = 32
    for gamma, amax, n, P, K, stages in _multiget_cells(quick):
        rng = np.random.default_rng(SEED)
        batches = [_skewed_batch(rng, n, P, K, gamma, amax)
                   for _ in range(stages)]
        f = fused_read("add", _finish_scale)
        cell = f"backend/multiget/zipf{gamma}/ar{amax}"
        wall = {}
        for kb in ("padded", "fused"):
            store = DataStore.create(K, P, value_width=width,
                                     chunk_words=width)
            store.write_rows(
                np.arange(K),
                np.random.default_rng(SEED + 1).standard_normal((K, width)))
            sess = Orchestrator(store, engine="tdorch", backend="jax",
                                kernel_backend=kb)

            def call():
                for tb in batches:
                    sess.run_stage(tb, f, write_back="add",
                                   return_results=True)

            wall[kb] = timeit(call, repeats=3, warmup=1)
            sess.reset_report()
            call()
            wpt = float(sess.report.sent.sum()) / (n * stages)
            rows.append(row(
                f"{cell}/{kb}", wall[kb] * 1e6,
                f"words_per_task={wpt:.3f};stages={stages}",
                seed=SEED, words_per_task=wpt, wall_ms=wall[kb] * 1e3))
        sp = wall["padded"] / wall["fused"]
        rows.append(row(f"{cell}/speedup", 0.0,
                        f"{sp:.2f}x fused vs padded wall", seed=SEED,
                        speedup=sp))

    # ---------------- PageRank through GraphSession ------------------------
    n = 20_000 if quick else 100_000
    attach = 8
    g = generators.barabasi_albert(n, attach, seed=SEED)
    og = ingest(g, P=8)
    for account in [False, True]:
        tag = "exec" if not account else "sim"
        cell = f"backend/pagerank/ba{n}/{tag}"
        wall = {}
        words = {}
        for backend in BACKENDS:
            def call():
                return pagerank(og, max_iter=8, tol=0.0, backend=backend,
                                account=account)

            wall[backend] = timeit(call, repeats=3, warmup=1)
            _, info = call()
            words[backend] = (float(info.report.sent.sum()) / g.m
                              if account else 0.0)
            metrics = {"wall_ms": wall[backend] * 1e3}
            if account:
                metrics["words_per_edge"] = words[backend]
            rows.append(row(
                f"{cell}/{backend}", wall[backend] * 1e6,
                f"8 iters;account={account}", seed=SEED, **metrics))
        sp = wall["numpy"] / wall["jax"]
        rows.append(row(f"{cell}/speedup", 0.0,
                        f"{sp:.2f}x jitted vs numpy wall", seed=SEED,
                        speedup=sp))
    return rows


if __name__ == "__main__":
    from .common import print_csv

    print_csv(run(quick=True))
