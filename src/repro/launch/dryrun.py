import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input shape) on the 16×16 single-pod and 2×16×16 multi-pod
production meshes, print memory/cost analysis, and record everything for
EXPERIMENTS.md §Dry-run / §Roofline.

The two lines above MUST precede any other import: jax locks the device
count at first initialization, and the production meshes need 512 host
placeholder devices. Run as its own process:

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from ..configs import all_arch_ids, get_config
from .hlo import parse_collectives
from .mesh import make_production_mesh
from .specs import SHAPES, input_specs, shape_applicable  # noqa: F401
from .steps import build_step


def run_cell(arch: str, shape: str, multi_pod: bool,
             overrides: Optional[Dict] = None, keep_hlo: bool = False
             ) -> Dict:
    cfg = get_config(arch)
    rec: Dict = {"arch": arch, "shape": shape,
                 "mesh": "2x16x16" if multi_pod else "16x16"}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        step = build_step(cfg, mesh, shape, **(overrides or {}))
        lowered = step.fn.lower(*step.arg_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        colls = parse_collectives(compiled.as_text())
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_per_device_bytes": (mem.argument_size_in_bytes
                                          + mem.temp_size_in_bytes
                                          + mem.output_size_in_bytes
                                          - mem.alias_size_in_bytes),
            },
            "cost": {
                "flops": cost.get("flops", 0.0),
                "bytes_accessed": cost.get("bytes accessed", 0.0),
                "transcendentals": cost.get("transcendentals", 0.0),
            },
            "collectives": {
                "wire_bytes_per_device": colls.wire_bytes,
                "count": colls.count,
                "by_kind": colls.by_kind,
            },
        })
        if keep_hlo:
            rec["hlo_lines"] = colls.lines
    except Exception as e:  # a failure here is a bug in our sharding
        rec["status"] = "FAILED"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["trace"] = traceback.format_exc()[-2000:]
    return rec


def _fmt_bytes(b):
    return f"{b / 2**30:.2f}GiB" if b > 2**29 else f"{b / 2**20:.1f}MiB"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="--arch <id> (see configs)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="every (arch × shape) cell")
    ap.add_argument("--out", default=None, help="write JSON records")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true")
    args = ap.parse_args()

    archs = all_arch_ids() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    overrides = {}
    if args.no_fsdp:
        overrides["fsdp"] = False
    if args.no_seq_parallel:
        overrides["sequence_parallel"] = False

    records = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape, multi, overrides=overrides)
                records.append(rec)
                tag = f"{arch:24s} {shape:12s} {rec['mesh']:8s}"
                if rec["status"] == "ok":
                    m = rec["memory"]
                    c = rec["cost"]
                    print(f"{tag} OK   mem/dev={_fmt_bytes(m['peak_per_device_bytes'])}"
                          f" flops={c['flops']:.3e}"
                          f" coll={_fmt_bytes(rec['collectives']['wire_bytes_per_device'])}"
                          f" compile={rec['compile_s']}s", flush=True)
                elif rec["status"] == "skipped":
                    print(f"{tag} SKIP {rec['reason'][:70]}", flush=True)
                else:
                    print(f"{tag} FAIL {rec['error'][:120]}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records to {args.out}")
    n_fail = sum(r["status"] == "FAILED" for r in records)
    if n_fail:
        raise SystemExit(f"{n_fail} cells FAILED")


if __name__ == "__main__":
    main()
