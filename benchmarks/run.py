"""Benchmark driver — one module per paper table/figure:

    Fig. 5   bench_ycsb       YCSB × Zipf × P, four engines + §4 geomeans
    Table 2  bench_graph      5 algorithms × 4 graph families vs direct
    Fig. 8/9 bench_scaling    strong + weak scaling (ER vs BA)
    Fig. 10  bench_breakdown  comm/compute/sync breakdown
    Tab. 3/4 bench_ablation   no-TD-Orch + T1/T2/T3 ablations
    (beyond) bench_skew       adaptive hot-chunk replication on vs off
    (beyond) bench_backend    numpy-oracle vs jitted-jax execution backend
    (beyond) bench_plan       StagePlan-driven rounds vs per-stage run_stage
    (beyond) bench_spmd       mesh-sharded backend: shard-count load balance
    (beyond) bench_kernels    per-kernel microbenchmarks
    (beyond) bench_serve      streaming serve: adaptive batching + overlap
    (beyond) bench_elastic    live migration under a nonstationary hot-set shift
    (beyond) bench_paramserve parameter-server tier: orchestrated MoE dispatch
                              + embedding serving vs naive (absorbs bench_moe)
    (beyond) bench_policy    engine="auto" adaptive loop vs fixed engines/modes

Prints ``name,us_per_call,derived`` CSV. `--quick` shrinks sizes ~10×.
`--json PATH` writes schema-versioned per-suite row files (fixed seeds, so
deterministic metrics are rerun-stable and regression-diffable — see
`benchmarks/check_regression.py`).
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

import jax

from . import (bench_ablation, bench_backend, bench_breakdown, bench_elastic,
               bench_graph, bench_kernels, bench_paramserve, bench_plan,
               bench_policy,
               bench_scaling, bench_serve, bench_skew, bench_spmd, bench_ycsb)
from .common import print_csv, write_json

SUITES = {
    "ycsb": bench_ycsb,
    "skew": bench_skew,
    "backend": bench_backend,
    "plan": bench_plan,
    "policy": bench_policy,
    "spmd": bench_spmd,
    "graph": bench_graph,
    "scaling": bench_scaling,
    "breakdown": bench_breakdown,
    "ablation": bench_ablation,
    "kernels": bench_kernels,
    "serve": bench_serve,
    "elastic": bench_elastic,
    "paramserve": bench_paramserve,
}


def main() -> None:
    # JAX reads JAX_COMPILATION_CACHE_DIR itself; otherwise keep compiled
    # programs at one fixed path (the path is part of the cache key)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update(
            "jax_compilation_cache_dir",
            str(pathlib.Path(__file__).resolve().parent.parent / ".jax_cache"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all", choices=["all", *SUITES])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write each suite's rows as PATH/BENCH_<suite>.json")
    args = ap.parse_args()
    names = list(SUITES) if args.suite == "all" else [args.suite]
    rows = []
    for name in names:
        t0 = time.time()
        suite_rows = SUITES[name].run(quick=args.quick)
        rows += suite_rows
        if args.json:
            out = write_json(args.json, name, suite_rows, quick=args.quick)
            print(f"# wrote {out}", file=sys.stderr)
        print(f"# suite {name} done in {time.time() - t0:.1f}s",
              file=sys.stderr)
    print_csv(rows)


if __name__ == "__main__":
    main()
