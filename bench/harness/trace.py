"""Profiler trace of the measured window, and its reduction to numbers.

The window is traced with ``jax.profiler`` (Python tracer off). The
reduction reads the ``.xplane.pb`` it writes into a flat list of events:

* device operations: the ``XLA Ops`` line of every ``/device:`` plane;
* the benchmark's own host spans: ``bench.*`` ``TraceAnnotation`` events.

From those: busy is the union of a device's operation intervals inside the
``bench.window`` span, averaged over the devices; the idle gaps are what
the union leaves of the window, each labelled by the benchmark span that
overlaps it most, which says what the host was doing meanwhile.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
NAME_CHARS = 200  # an operation's name is its HLO text; keep the head


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load_events(log_dir: str, log=sys.stderr) -> list:
    """[{"plane", "line", "name", "start_ns", "dur_ns"}] of the device
    operations and the benchmark spans in the trace under `log_dir`."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no trace under {log_dir}")
    out = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            lines = list(plane.lines)
            device = plane.name.startswith("/device:")
            if device or plane.name.startswith("/host:"):
                print(f"trace plane {plane.name}: " + ", ".join(
                    f"{ln.name}" for ln in lines[:12]), file=log)
            for ln in lines:
                if device and ln.name != OPS_LINE:
                    continue
                for ev in ln.events:
                    if device or ev.name.startswith(SPAN_PREFIX):
                        out.append({"plane": plane.name, "line": ln.name,
                                    "name": ev.name,
                                    "start_ns": float(ev.start_ns),
                                    "dur_ns": float(ev.duration_ns)})
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(events: list, top: int = 10) -> dict:
    """Busy and idle time of the devices inside the window span."""
    spans = [e for e in events if not e["plane"].startswith("/device:")]
    win = [e for e in spans if e["name"] == WINDOW]
    if not win:
        raise RuntimeError("the trace holds no bench.window span")
    w0 = min(e["start_ns"] for e in win)
    w1 = max(e["start_ns"] + e["dur_ns"] for e in win)
    by_dev = defaultdict(list)
    op_ns = defaultdict(float)
    for e in events:
        if not e["plane"].startswith("/device:"):
            continue
        s = max(e["start_ns"], w0)
        t = min(e["start_ns"] + e["dur_ns"], w1)
        by_dev[e["plane"]]  # a device with no op in the window still counts
        if t > s:
            by_dev[e["plane"]].append((s, t))
            op_ns[e["name"]] += t - s
    if not by_dev:
        raise RuntimeError("the trace holds no device operation")
    devs = sorted(by_dev)
    unions = {d: _union(by_dev[d]) for d in devs}
    busy = {d: sum(e - s for s, e in unions[d]) for d in devs}
    mean_busy = sum(busy.values()) / len(devs)

    # idle gaps of the first device, labelled by the host span over them
    gaps, edge = [], w0
    for s, e in unions[devs[0]] + [[w1, w1]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    host = [e for e in spans if e["name"] != WINDOW]

    def label(g0, g1):
        best, name = 0.0, "none"
        for e in host:
            ov = min(g1, e["start_ns"] + e["dur_ns"]) - max(g0, e["start_ns"])
            if ov > best:
                best, name = ov, e["name"]
        return name

    labelled = sorted(((label(a, b), (b - a) * 1e-9) for a, b in gaps),
                      key=lambda x: -x[1])
    idle_by_label = defaultdict(float)
    for name, s in labelled:
        idle_by_label[name] += s
    ops = sorted(op_ns.items(), key=lambda x: -x[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": mean_busy * 1e-9,
        "busy_s_by_device": {d: busy[d] * 1e-9 for d in devs},
        "device_ops": [[n[:NAME_CHARS], ns * 1e-9 / len(devs)]
                       for n, ns in ops],
        "idle_gaps": [[n, s] for n, s in labelled[:top]],
        "idle_s_by_host_span": dict(idle_by_label),
    }


def save_events(events: list, path: str, limit: int = 2000) -> None:
    """A small recorded trace: the window span and the first `limit`
    device operations and spans inside it."""
    win = [e for e in events if e["name"] == WINDOW]
    rest = sorted((e for e in events if e["name"] != WINDOW),
                  key=lambda e: e["start_ns"])[:limit]
    with open(path, "w") as f:
        json.dump(win + rest, f)
