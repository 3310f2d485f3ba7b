#!/usr/bin/env python3
"""Smoke run of the TD-Orch device path on a TPU, through the public API.

    python chip_smoke.py             # one chip: YCSB-A/C, fused multi-get,
                                     # PageRank
    python chip_smoke.py --chips 4   # four chips: YCSB-A on backend
                                     # "jax_spmd" against the one-chip run

Every phase checks its answers against a plain numpy reference written
here, prints one JSON line (routes each kernel took, stages routed to the
host, smoke timings — wall seconds around work that ends in a copy to the
host or `block_until_ready`, compiles included, not benchmark numbers),
and the last line is
``{"ok": true, "device": {...}}``. Without a TPU it exits non-zero and
prints no result: there is no CPU fallback.

Data is made from ``--seed``. Compiled programs go to
``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise to ``.jax_cache/``
next to this file.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

# YCSB core workloads: 10 fields × 100 B = 1 KB records, Zipfian keys with
# the default constant 0.99
YCSB_WIDTH = 250  # float32 words per 1 KB record
YCSB_GAMMA = 0.99
YCSB_OPS = 1 << 16  # operations per batch
YCSB_BATCHES = 3
RTOL, ATOL = 2e-4, 1e-5  # float32 device path vs float64 reference
PLATFORM = "tpu"  # where the tables must live


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def max_err(got: np.ndarray, want: np.ndarray, rows: int = 1 << 16) -> float:
    """Largest |got - want| beyond the tolerance band (0.0 = within it),
    row-chunked so a GiB-sized table needs no GiB-sized temporaries."""
    worst = 0.0
    for i in range(0, want.shape[0], rows):
        g = np.asarray(got[i:i + rows], dtype=np.float64)
        w = want[i:i + rows]
        excess = np.abs(g - w) - (ATOL + RTOL * np.abs(w))
        worst = max(worst, float(excess.max(initial=0.0)))
        check(np.isfinite(g).all(), "non-finite values")
    return worst


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def route_diff(before: dict, after: dict) -> dict:
    """{kernel: [routes built since `before`]}"""
    out: dict = {}
    for (kernel, route), n in sorted(after.items()):
        if n > before.get((kernel, route), 0):
            out.setdefault(kernel, []).append(route)
    return out


# ---------------------------------------------------------------------------
# YCSB on a DistributedHashTable
# ---------------------------------------------------------------------------
def ycsb_reference(table, keys, is_read, operand):
    """One batch on the plain table: every op reads the pre-batch row; per
    key, the UPDATE with the lowest task index lands its multiply-and-add."""
    got = table[keys]
    upd = np.flatnonzero(~is_read)
    uk, first = np.unique(keys[upd], return_index=True)
    win = upd[first]
    table[uk] = table[uk] * operand[win, :1] + operand[win, 1:2]
    return got


def load_table(num_keys: int, P: int, seed: int, backend_tables: int = 1):
    from repro.kvstore import DistributedHashTable

    rng = np.random.default_rng(seed)
    init = rng.standard_normal((num_keys, YCSB_WIDTH), dtype=np.float32)
    tables = []
    for _ in range(backend_tables):
        ht = DistributedHashTable(num_keys, P, value_width=YCSB_WIDTH)
        ht.bulk_load(np.arange(num_keys), init)
        tables.append(ht)
    return tables, init.astype(np.float64)


def run_ycsb(tables, backends, ref, workload: str, P: int, seed: int):
    """Drive YCSB_BATCHES batches through each (table, backend) pair and
    check every result and every table row against the reference. Returns
    per-backend smoke timings (s per batch)."""
    from repro.kvstore import make_ycsb_batch

    timings = {b: [] for b in backends}
    for i in range(YCSB_BATCHES):
        keys, is_read, operand = make_ycsb_batch(
            workload, YCSB_OPS // P, P, ref.shape[0], gamma=YCSB_GAMMA,
            seed=seed + 1000 * i + ord(workload))
        want = ycsb_reference(ref, keys, is_read, operand)
        for ht, backend in zip(tables, backends):
            t0 = time.perf_counter()
            res = ht.execute_batch(keys, is_read, operand, engine="tdorch",
                                   backend=backend)
            # the ⊙-apply may still run after the results reached the host
            ht.session("tdorch", backend=backend).backend.sync(ht.store)
            timings[backend].append(time.perf_counter() - t0)
            err = max_err(res.values, want)
            check(err == 0.0, f"YCSB-{workload} batch {i} {backend}: GET "
                  f"values off the reference by {err}")
            err = max_err(ht.values, ref)
            check(err == 0.0, f"YCSB-{workload} batch {i} {backend}: table "
                  f"rows off the reference by {err}")
    return timings


def ycsb_phase(seed: int, num_keys: int = 1 << 20, P: int = 8) -> None:
    from repro.kernels import routes

    (ht,), ref = load_table(num_keys, P, seed)
    for wl in ("A", "C"):
        before = routes.traced()
        timings = run_ycsb([ht], ["jax"], ref, wl, P, seed)
        backend = ht.session("tdorch", backend="jax").backend
        dv = backend.resident(ht.store)
        check(dv is not None, "no device-resident table")
        devs = list(dv.devices())
        check(len(devs) == 1 and devs[0].platform == PLATFORM,
              f"table buffer is on {devs}, not one TPU")
        err = max_err(np.asarray(dv), ref)
        check(err == 0.0, f"device table off the reference by {err}")
        check(backend.host_stages == 0,
              f"{backend.host_stages} stages routed to the host")
        stats = devs[0].memory_stats() or {}
        emit(f"ycsb-{wl}", ok=True, keys=num_keys, record_bytes=1000,
             value_width=YCSB_WIDTH, zipf=YCSB_GAMMA, machines=P,
             batches=YCSB_BATCHES, ops_per_batch=YCSB_OPS,
             table_device=str(devs[0]), table_bytes=int(dv.nbytes),
             bytes_in_use=stats.get("bytes_in_use"),
             routes=route_diff(before, routes.traced()),
             host_stages=backend.host_stages,
             smoke_timing_s_per_batch=timings["jax"])


# ---------------------------------------------------------------------------
# skewed ragged multi-get with a fused-able lambda, add write-back
# ---------------------------------------------------------------------------
def _finish_scale(c, r):
    return r * c[:, :1]


def _zipf_keys(rng, K, size, gamma):
    cdf = np.cumsum(np.arange(1, K + 1, dtype=np.float64) ** (-gamma))
    return np.searchsorted(cdf / cdf[-1], rng.random(size)).astype(np.int64)


def skewed_batch(rng, n, P, K, gamma, amax):
    """~10% of tasks read `amax` Zipf-hot chunks, the rest read one; half
    the tasks write back to their first read key. Contexts are in [0, 1)."""
    from repro.core import TaskBatch

    arity = np.where(rng.random(n) < 0.1, amax, 1).astype(np.int64)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(arity, out=indptr[1:])
    indices = _zipf_keys(rng, K, int(indptr[-1]), gamma)
    write_keys = np.where(rng.random(n) < 0.5, indices[indptr[:-1]], -1)
    return TaskBatch(contexts=rng.random((n, 2)),
                     origin=rng.integers(0, P, n).astype(np.int64),
                     write_keys=write_keys, read_indptr=indptr,
                     read_indices=indices)


def multiget_phase(seed: int, n: int = 4096, width: int = 32, P: int = 8,
                   gamma: float = 1.2, amax: int = 64) -> None:
    from repro.core import DataStore, Orchestrator, fused_read
    from repro.kernels import routes
    from repro.kernels.stage_fused.ops import fits_pallas

    # the largest power-of-two table the fused kernel admits when every
    # task of the bucketed batch writes its own segment
    segs = 1 << (n - 1).bit_length()
    K = 1 << 10
    while fits_pallas(2 * K, width, segs, 2 * n):
        K *= 2
    rng = np.random.default_rng(seed)
    # non-negative values and contexts: every sum is free of cancellation,
    # so the float32 device path is held to the elementwise tolerance even
    # on hot rows that grow by hundreds of add write-backs
    init = rng.random((K, width))
    stores = {}
    for b in ("numpy", "jax"):
        stores[b] = DataStore.create(K, P, value_width=width,
                                     chunk_words=width)
        stores[b].write_rows(np.arange(K), init)
    # ragged batches take the fused stage kernel; the arity-1 batch takes
    # the flat stage, whose add write-back is the segment_add combine
    batches = [skewed_batch(rng, n, P, K, gamma, a)
               for a in (amax, amax, 1, amax)]
    f = fused_read("add", _finish_scale)
    sess = {b: Orchestrator(stores[b], engine="tdorch", backend=b)
            for b in stores}
    before = routes.traced()
    timings = []
    for i, tb in enumerate(batches):
        want = sess["numpy"].run_stage(tb, f, write_back="add",
                                       return_results=True).results
        t0 = time.perf_counter()
        got = sess["jax"].run_stage(tb, f, write_back="add",
                                    return_results=True).results
        sess["jax"].backend.sync(stores["jax"])
        timings.append(time.perf_counter() - t0)
        err = max_err(got, np.asarray(want))
        check(err == 0.0, f"multi-get batch {i}: results off the oracle by "
              f"{err}")
        err = max_err(stores["jax"].values, stores["numpy"].values)
        check(err == 0.0, f"multi-get batch {i}: table off the oracle by "
              f"{err}")
    backend = sess["jax"].backend
    check(backend.host_stages == 0,
          f"{backend.host_stages} stages routed to the host")
    emit("multiget", ok=True, keys=K, value_width=width, tasks=n,
         max_arity=amax, zipf=gamma, batches=len(batches),
         pairs=[int(tb.nnz) for tb in batches],
         routes=route_diff(before, routes.traced()),
         host_stages=backend.host_stages, smoke_timing_s_per_batch=timings)


# ---------------------------------------------------------------------------
# PageRank through a GraphSession
# ---------------------------------------------------------------------------
def pagerank_reference(src, dst, n, alpha, iters):
    """Plain power iteration; dangling mass spread uniformly."""
    deg = np.bincount(src, minlength=n).astype(np.float64)
    pr = np.full(n, 1.0 / n)
    for _ in range(iters):
        contrib = np.divide(pr, deg, out=np.zeros(n), where=deg > 0)
        nxt = np.full(n, (1.0 - alpha) / n + alpha * pr[deg == 0].sum() / n)
        nxt += alpha * np.bincount(dst, weights=contrib[src], minlength=n)
        pr = nxt
    return pr


def pagerank_phase(seed: int, n: int = 1 << 20, attach: int = 8,
                   iters: int = 20, P: int = 8) -> None:
    from repro.graph import GraphSession, barabasi_albert, ingest, pagerank
    from repro.kernels import routes

    t0 = time.perf_counter()
    g = barabasi_albert(n, attach, seed=seed)
    og = ingest(g, P=P)
    ingest_s = time.perf_counter() - t0
    sess = GraphSession(og, backend="jax")
    before = routes.traced()
    t0 = time.perf_counter()
    pr, info = pagerank(og, max_iter=iters, tol=0.0, session=sess)
    run_s = time.perf_counter() - t0
    want = pagerank_reference(g.src, g.dst, n, 0.85, iters)
    l1 = float(np.abs(pr - want).sum())
    check(np.isfinite(pr).all() and info.rounds == iters,
          "PageRank did not finish")
    check(l1 < 1e-4, f"PageRank L1 distance to the reference is {l1}")
    check(sess.backend.host_stages == 0,
          f"{sess.backend.host_stages} stages routed to the host")
    emit("pagerank", ok=True, vertices=n, edges=int(g.m), attach=attach,
         iters=iters, l1_to_reference=l1,
         max_abs_err=float(np.abs(pr - want).max()),
         routes=route_diff(before, routes.traced()),
         host_stages=sess.backend.host_stages,
         host_syncs=sess.backend.host_syncs,
         smoke_timing_s={"generate_and_ingest": ingest_s, "pagerank": run_s})


# ---------------------------------------------------------------------------
# four chips: YCSB-A on the mesh-sharded backend
# ---------------------------------------------------------------------------
def spmd_phase(seed: int, chips: int, keys_per_chip: int = 1 << 20) -> None:
    import jax

    from repro.kernels import routes

    P = chips
    num_keys = chips * keys_per_chip  # 2^20 records: ~1 GiB per chip
    (ht_one, ht_mesh), ref = load_table(num_keys, P, seed, backend_tables=2)
    before = routes.traced()
    timings = run_ycsb([ht_one, ht_mesh], ["jax", "jax_spmd"], ref, "A", P,
                       seed)
    # the two backends agree row for row (each already matches the
    # reference within tolerance)
    err = max_err(ht_mesh.values, ht_one.values)
    check(err == 0.0, f"jax_spmd table off the one-chip table by {err}")
    spmd = ht_mesh.session("tdorch", backend="jax_spmd").backend
    slabs = spmd.resident(ht_mesh.store)
    check(slabs is not None, "no sharded table")
    lay = ht_mesh.store.shard_layout()
    shards = sorted(slabs.addressable_shards, key=lambda s: s.device.id)
    check(len({s.device for s in shards}) == P,
          f"slabs on {len(shards)} devices, want {P}")
    per_chip = []
    for s in shards:
        m = s.index[0].start
        # a device shard is padded to the TPU's tile past the layout's rows
        rows = np.asarray(s.data)[0, :lay.slab_rows, :YCSB_WIDTH]
        live = lay.slab_keys[m] < num_keys
        err = max_err(rows[live], ref[lay.slab_keys[m][live]])
        check(err == 0.0, f"shard {m} on {s.device} off the reference")
        per_chip.append({"device": str(s.device), "machine": int(m),
                         "rows": int(live.sum()),
                         "bytes": int(s.data.nbytes)})
    check(spmd.host_stages == 0,
          f"{spmd.host_stages} stages routed to the host")
    emit("ycsb-A-spmd", ok=True, keys=num_keys, value_width=YCSB_WIDTH,
         zipf=YCSB_GAMMA, machines=P, batches=YCSB_BATCHES,
         ops_per_batch=YCSB_OPS, shards=per_chip,
         bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use")
                       for d in jax.devices()[:P]],
         routes=route_diff(before, routes.traced()),
         host_stages=spmd.host_stages,
         stage_work_ratio=[st.work_ratio() for st in spmd.stage_stats],
         smoke_timing_s_per_batch=timings)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    # JAX reads JAX_COMPILATION_CACHE_DIR itself; otherwise one fixed path
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        print(f"chip_smoke.py: no TPU (JAX found {devs[0].platform}); "
              "there is no CPU fallback", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1

    if args.chips == 1:
        phases = [("ycsb", ycsb_phase), ("multiget", multiget_phase),
                  ("pagerank", pagerank_phase)]
    else:
        phases = [("ycsb-spmd",
                   lambda seed: spmd_phase(seed, args.chips))]
    failed = []
    for name, phase in phases:
        # one failing phase must not hide what the others would show
        try:
            phase(args.seed)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"chip_smoke.py: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
