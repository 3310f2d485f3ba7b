#!/usr/bin/env python3
"""One run of one benchmark cell of BENCHMARK.json.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell names a configuration (``bench/configs/<config>.json``), whose
``system`` names the driver in ``bench/systems/``, and a traffic mix
(``bench/traffic/<traffic>.json``). A run makes its data from the seed,
loads it, warms up every shape, then calls the system back to back for
``--seconds`` (one client, closed loop), checks what the window produced
against the plain reference, and prints one JSON line last on standard
output. With ``--trace 1`` the window is traced and the line carries the
cell's per-layer metrics, each read by ``bench/metrics/<name>.py``;
otherwise its end-to-end metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result. ``--control 1`` also reports the control (the reference
in bfloat16 in the program's place) under ``control``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "metrics"))

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    pass


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def peaks(kind: str) -> dict:
    table = read_json(BENCH / "metrics" / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device {kind!r} is not in bench/metrics/peaks.json")
    return table[kind]


def start_jax(chips: int, require_chip: bool):
    """Import JAX with the compile cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says) and check the devices."""
    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devs[0].platform}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
        peaks(devs[0].device_kind)
    return jax, devs


class CompileCounter:
    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == COMPILE_EVENT:
            self.n += 1


def run_cell(spec: dict, name: str, seed: int, seconds: float, trace: bool,
             control: bool = False, require_chip: bool = True,
             configs: pathlib.Path = BENCH / "configs",
             traffic: pathlib.Path = BENCH / "traffic",
             events_path: str | None = None, log=sys.stderr) -> dict:
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = read_json(configs / f"{cell['config']}.json")
    mix = read_json(traffic / f"{cell['traffic']}.json")
    chips = int(cell["chips"])
    jax, devs = start_jax(chips, require_chip)
    sys.path.insert(0, str(ROOT / "src"))
    from harness import trace as tr

    compiles = CompileCounter(jax)
    t_jax = time.perf_counter()
    system = load_module(BENCH / "systems" / f"{cfg['system']}.py").System(
        cfg, mix, seed)
    t_built = time.perf_counter()
    system.warm()
    parts = {"start": t_jax - T_START, **system.setup_parts,
             "warm": time.perf_counter() - t_built}
    print("set-up seconds: " + json.dumps(parts), file=log)
    before = system.counters()

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        tr.start(tdir)
    lat, calls, error = [], 0, None
    compiled_before = compiles.n
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < seconds:
            ts = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.call"):
                    out = system.call(calls)
                with jax.profiler.TraceAnnotation("bench.sync"):
                    system.sync(out)
            except Exception:
                error = traceback.format_exc()
                break
            lat.append(time.perf_counter() - ts)
            with jax.profiler.TraceAnnotation("bench.record"):
                system.record(calls, out)
            calls += 1
    elapsed = time.perf_counter() - t0
    in_window = compiles.n - compiled_before
    if trace:
        tr.stop()
    setup_s = t0 - T_START
    print(f"compiles in the window: {in_window}", file=log)
    print(f"window: {calls} calls in {elapsed!r} s, set-up {setup_s!r} s",
          file=log)
    used = devs[:chips]
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)
    after = system.counters()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}

    units = system.call_units
    attempted = calls * units + (units if error else 0)
    failed = units if error else 0
    if after["host_stages"]:
        failed = attempted
    metrics, breakdown = {}, None
    if trace:
        events = tr.load_events(tdir, log)
        shutil.rmtree(tdir, ignore_errors=True)
        if events_path:
            tr.save_events(events, events_path)
        red = tr.reduce(events)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        print("idle seconds by host span: "
              + json.dumps(red["idle_s_by_host_span"]), file=log)
        ctx = SimpleNamespace(trace=red, before=before, after=after,
                              calls=calls, work=system.window_work(calls),
                              peaks=peaks(devs[0].device_kind)
                              if require_chip else {})
        for m in spec["per_layer"]:
            if not applies(m, name):
                continue
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        got = system.window_metrics(calls, elapsed, lat) if calls else {}
        got["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            if applies(m, name) and m["name"] in got:
                metrics[m["name"]] = {"value": got[m["name"]],
                                      "unit": m["unit"]}

    # the check runs once the program's state is freed
    checks, ctl = {}, None
    try:
        system.collect()
        gc.collect()
        checks, ctl = system.check(control)
    except Exception:
        error = (error or "") + traceback.format_exc()
    limits = mix["limits"]
    correct = (error is None and not after["host_stages"] and calls > 0
               and bool(checks)
               and all(k in limits and v <= limits[k] for k, v in checks.items()))
    if error:
        print(error, file=log)
    if after["host_stages"]:
        print(f"{after['host_stages']} stages ran on the host oracle", file=log)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compiles_in_window"] = in_window
    if ctl is not None:
        line["control"] = {k: {"value": v, "limit": limits.get(k)}
                           for k, v in ctl.items()}
    line["checks"] = {k: {"value": v, "limit": limits.get(k)}
                      for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k} {v!r} limit {limits.get(k)!r}", file=log)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-events", default=None,
                    help="write the traced events of the window here")
    args = ap.parse_args(argv)
    spec = read_json(ROOT / "BENCHMARK.json")
    try:
        line = run_cell(spec, args.workload, args.seed, args.seconds,
                        bool(args.trace), control=bool(args.control),
                        events_path=args.trace_events)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # leave at once: tearing down gigabytes of device buffers can stall
    os._exit(rc)
