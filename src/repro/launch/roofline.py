import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
"""Roofline analysis (deliverable g).

Hardware model (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
Terms (all per-chip — post-SPMD cost_analysis and HLO are per-device):

    compute    = HLO_FLOPs / peak_FLOPs
    memory     = HLO_bytes / HBM_bw
    collective = collective_wire_bytes / ICI_link_bw

XLA's cost_analysis does NOT multiply while-loop (scan) bodies by trip
count, so per-cell costs are extracted from UNROLLED compiles at two reduced
depths and extrapolated linearly — exact for homogeneous layer stacks:
    per_layer = (cost(L2) − cost(L1)) / (L2 − L1);  total = intercept + L·per_layer
(the same arch/width/sharding; only depth changes). Memory numbers come from
the full scan-compile dry-run, which IS trip-count correct.

MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params — the
"useful" fraction MODEL_FLOPS / HLO_FLOPs flags remat/redundancy waste
(remat recompute legitimately pushes it below 1; ratios ≪ 0.5 mean waste).
"""
import argparse
import dataclasses
import json
import traceback
from typing import Dict, Optional

import jax

from ..configs import all_arch_ids, get_config
from .hlo import parse_collectives
from .mesh import make_production_mesh
from .specs import SHAPES, input_specs, shape_applicable
from .steps import build_step

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s / link

# unrolled probe depths per pattern (must keep hybrid cadence intact)
_PROBE_DEPTHS = {
    "dense": (2, 4), "parallel": (2, 4), "moe": (2, 4),
    "zamba2": (6, 12), "xlstm": (8, 16),
}


def _with_depth(cfg, n):
    return dataclasses.replace(cfg, n_layers=n)


def _costs_of(cfg, shape, mesh, overrides) -> Dict[str, float]:
    step = build_step(cfg, mesh, shape, scan_layers=False, **(overrides or {}))
    compiled = step.fn.lower(*step.arg_specs).compile()
    cost = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(colls.wire_bytes),
    }


def model_flops_per_chip(cfg, shape, n_chips: int) -> float:
    info = SHAPES[shape]
    tokens = info["batch"] * (info["seq"] if info["kind"] == "train" else
                              (info["seq"] if info["kind"] == "prefill" else 1))
    n = cfg.active_param_count()
    mult = 6.0 if info["kind"] == "train" else 2.0
    return mult * n * tokens / n_chips


def analyze_cell(arch: str, shape: str, overrides: Optional[Dict] = None,
                 multi_pod: bool = False, cfg_transform=None) -> Dict:
    cfg = get_config(arch)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    rec: Dict = {"arch": arch, "shape": shape,
                 "mesh": "2x16x16" if multi_pod else "16x16"}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = 512 if multi_pod else 256
        l1, l2 = _PROBE_DEPTHS[cfg.pattern]
        c1 = _costs_of(_with_depth(cfg, l1), shape, mesh, overrides)
        c2 = _costs_of(_with_depth(cfg, l2), shape, mesh, overrides)
        total = {}
        for k in ("flops", "bytes", "coll"):
            per_layer = (c2[k] - c1[k]) / (l2 - l1)
            intercept = c1[k] - per_layer * l1
            total[k] = max(intercept + per_layer * cfg.n_layers, 0.0)
        terms = {
            "compute_s": total["flops"] / PEAK_FLOPS,
            "memory_s": total["bytes"] / HBM_BW,
            "collective_s": total["coll"] / ICI_BW,
        }
        dominant = max(terms, key=terms.get)
        mf = model_flops_per_chip(cfg, shape, n_chips)
        rec.update({
            "status": "ok",
            "hlo_flops": total["flops"],
            "hlo_bytes": total["bytes"],
            "coll_bytes": total["coll"],
            **terms,
            "dominant": dominant.replace("_s", ""),
            "model_flops": mf,
            "useful_ratio": mf / max(total["flops"], 1.0),
            # achievable step time ≈ max of the three terms (perfect overlap)
            "roofline_s": max(terms.values()),
            "mfu_bound": mf / PEAK_FLOPS / max(max(terms.values()), 1e-12),
        })
    except Exception as e:
        rec["status"] = "FAILED"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["trace"] = traceback.format_exc()[-1500:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--no-tp", action="store_true",
                    help="pure-DP preset (train shapes only)")
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None,
                    help="override SSD/mLSTM chunk length")
    ap.add_argument("--intra-bf16", action="store_true",
                    help="bf16 intra-chunk SSD tensors")
    ap.add_argument("--moe-gemm", default=None, choices=["ragged", "binned"])
    ap.add_argument("--moe-hot", type=int, default=None)
    ap.add_argument("--moe-capacity", type=float, default=None)
    ap.add_argument("--moe-dispatch", default=None,
                    choices=["tdorch", "push", "pull"])
    args = ap.parse_args()
    archs = [args.arch] if args.arch else all_arch_ids()
    shapes = [args.shape] if args.shape else list(SHAPES)
    overrides = {}
    if args.no_fsdp:
        overrides["fsdp"] = False
    if args.no_seq_parallel:
        overrides["sequence_parallel"] = False
    records = []
    no_tp = args.no_tp
    for arch in archs:
        for shape in shapes:
            ov = dict(overrides)
            if SHAPES[shape]["kind"] == "train":
                if args.grad_accum is not None:
                    ov["grad_accum"] = args.grad_accum
                if no_tp:
                    ov["tp"] = False
            def _tf(cfg, a=args):
                if cfg.ssm is not None and (a.chunk or a.intra_bf16):
                    cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(
                        cfg.ssm,
                        chunk=a.chunk or cfg.ssm.chunk,
                        intra_dtype=("bfloat16" if a.intra_bf16
                                     else cfg.ssm.intra_dtype)))
                if cfg.xlstm is not None and a.chunk:
                    cfg = dataclasses.replace(cfg, xlstm=dataclasses.replace(
                        cfg.xlstm, chunk=a.chunk))
                if cfg.moe is not None and (a.moe_gemm or a.moe_hot is not None
                                            or a.moe_capacity or a.moe_dispatch):
                    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
                        cfg.moe,
                        gemm_impl=a.moe_gemm or cfg.moe.gemm_impl,
                        num_hot=(a.moe_hot if a.moe_hot is not None
                                 else cfg.moe.num_hot),
                        capacity_factor=a.moe_capacity
                        or cfg.moe.capacity_factor,
                        dispatch=a.moe_dispatch or cfg.moe.dispatch))
                return cfg

            rec = analyze_cell(arch, shape, ov or None, cfg_transform=_tf)
            records.append(rec)
            if rec["status"] == "ok":
                print(f"{arch:24s} {shape:12s} "
                      f"compute={rec['compute_s']*1e3:8.2f}ms "
                      f"memory={rec['memory_s']*1e3:8.2f}ms "
                      f"coll={rec['collective_s']*1e3:8.2f}ms "
                      f"dom={rec['dominant']:10s} "
                      f"useful={rec['useful_ratio']:.2f} "
                      f"mfu_bound={rec['mfu_bound']:.2f}", flush=True)
            else:
                print(f"{arch:24s} {shape:12s} {rec['status']} "
                      f"{rec.get('reason', rec.get('error', ''))[:80]}",
                      flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)


if __name__ == "__main__":
    main()
