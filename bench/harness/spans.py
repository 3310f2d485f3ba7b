"""The program's own spans in the traced window, and the idle time they
explain.

The orchestrator names its host steps with ``tdorch.*`` spans
(``src/repro/core/spans.py``). They land in the profiler trace that
``trace.py`` records, on the clock of the device's ``XLA Ops`` line. This
module reads them on top of ``trace.py``:

* `load_events` returns the events ``trace.load_events`` returns, plus every
  host event named ``tdorch.*`` with its arguments under ``"args"`` and the
  device's ``XLA Modules`` events (one a run of a compiled program).
* `reduce` returns ``trace.reduce`` of the events without the program's, so
  every key it has reads exactly as before, plus:

  - ``host_self_s_by_span``: per program span name, its time in the window
    less what its child spans on the same thread cover;
  - ``span_count``: the program spans of each name in the window;
  - ``idle_s_by_span``: each idle nanosecond of the first device, given to
    the innermost span open at that instant (a ``bench.*`` span where no
    program span is open, ``none`` where no span is);
  - ``device_s_by_program``: device time per compiled program (the
    ``XLA Modules`` name without its fingerprint, e.g. ``jit_apply_rows``),
    averaged over the devices like ``device_ops``. On a TPU v5e the
    ``XLA Ops`` events carry no op name path, so the program's named scopes
    cannot be read per operation from the trace;
  - ``idle_gaps`` relabelled: each gap by the span that is innermost over
    the largest part of it, which is its ``bench.*`` label where no program
    span covers any of it.
"""
from __future__ import annotations

import glob
import heapq
import os
import sys
from collections import defaultdict

from harness import trace

PROGRAM_PREFIX = "tdorch."
MODULES_LINE = "XLA Modules"


def load_events(log_dir: str, log=sys.stderr) -> list:
    """trace.load_events' events (in one pass over the trace), the
    program's spans with their arguments and the device's program runs."""
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
                if device and ln.name not in (trace.OPS_LINE, MODULES_LINE):
                    continue
                for ev in ln.events:
                    program = ev.name.startswith(PROGRAM_PREFIX)
                    if not (device or program
                            or ev.name.startswith(trace.SPAN_PREFIX)):
                        continue
                    e = {"plane": plane.name, "line": ln.name,
                         "name": ev.name, "start_ns": float(ev.start_ns),
                         "dur_ns": float(ev.duration_ns)}
                    if program and not device:
                        e["args"] = dict(ev.stats)
                    out.append(e)
    return out


def _is_program(e) -> bool:
    return (not e["plane"].startswith("/device:")
            and e["name"].startswith(PROGRAM_PREFIX))


def _self_times(spans, w0, w1):
    """(self seconds by name, count by name) of spans clipped to [w0, w1];
    spans on one thread nest, so a span's parent is the innermost one open
    at its start on the same line."""
    self_ns, count = defaultdict(float), defaultdict(int)
    by_line = defaultdict(list)
    for e in spans:
        s = max(e["start_ns"], w0)
        t = min(e["start_ns"] + e["dur_ns"], w1)
        if t > s:
            by_line[(e["plane"], e["line"])].append((s, -t, e["name"]))
    for items in by_line.values():
        stack = []  # [end, name]
        for s, neg_t, name in sorted(items):
            t = -neg_t
            while stack and stack[-1][0] <= s:
                stack.pop()
            self_ns[name] += t - s
            count[name] += 1
            if stack:
                self_ns[stack[-1][1]] -= t - s
            stack.append([t, name])
    return ({n: ns * 1e-9 for n, ns in self_ns.items()}, dict(count))


def _innermost_idle(spans, gaps):
    """Per gap, {span name: idle ns} given to the innermost span open (the
    one that started last) at each instant of the gap, ``none`` where no
    span is open."""
    ends = [e["start_ns"] + e["dur_ns"] for e in spans]
    points = sorted([(e["start_ns"], 1, i) for i, e in enumerate(spans)]
                    + [(t, 0, i) for i, t in enumerate(ends)])
    cuts = sorted({t for t, _, _ in points} | {t for g in gaps for t in g})
    out = [defaultdict(float) for _ in gaps]
    open_, ended = [], set()  # heap of (-start, end, index)
    p = g = 0
    for a, b in zip(cuts, cuts[1:]):
        while p < len(points) and points[p][0] <= a:
            _, starts, i = points[p]
            if starts:
                heapq.heappush(open_, (-spans[i]["start_ns"], ends[i], i))
            else:
                ended.add(i)
            p += 1
        while open_ and open_[0][2] in ended:
            heapq.heappop(open_)
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g < len(gaps) and gaps[g][0] <= a:
            out[g][spans[open_[0][2]]["name"] if open_ else "none"] += b - a
    return out


def _overlap_label(bench, g0, g1) -> str:
    """trace.reduce's label of a gap: the span that overlaps it most."""
    best, name = 0.0, "none"
    for e in bench:
        ov = min(g1, e["start_ns"] + e["dur_ns"]) - max(g0, e["start_ns"])
        if ov > best:
            best, name = ov, e["name"]
    return name


def reduce(events: list, top: int = 10) -> dict:
    """trace.reduce of the events without the program's, plus the program's
    spans and the device time of its compiled programs (see the module's
    docstring)."""
    plain = [e for e in events
             if not _is_program(e) and e["line"] != MODULES_LINE]
    red = trace.reduce(plain, top)
    spans = [e for e in events if _is_program(e)]
    win = [e for e in plain if e["name"] == trace.WINDOW]
    w0 = min(e["start_ns"] for e in win)
    w1 = max(e["start_ns"] + e["dur_ns"] for e in win)
    self_s, count = _self_times(spans, w0, w1)

    devs = sorted({e["plane"] for e in plain
                   if e["plane"].startswith("/device:")})
    by_program = defaultdict(float)
    for e in events:
        if e["line"] != MODULES_LINE or not e["plane"].startswith("/device:"):
            continue
        s = max(e["start_ns"], w0)
        t = min(e["start_ns"] + e["dur_ns"], w1)
        if t > s:
            by_program[e["name"].split("(")[0]] += (t - s) * 1e-9 / len(devs)

    # the first device's idle gaps, as trace.reduce finds them
    busy = trace._union([
        (max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1))
        for e in plain if e["plane"] == devs[0]
        and e["start_ns"] + e["dur_ns"] > w0 and e["start_ns"] < w1])
    gaps, edge = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    bench = [e for e in plain if not e["plane"].startswith("/device:")
             and e["name"] != trace.WINDOW]
    per_gap = _innermost_idle(bench + spans, gaps)
    idle_by_span = defaultdict(float)
    labelled = []
    for (g0, g1), parts in zip(gaps, per_gap):
        for name, ns in parts.items():
            idle_by_span[name] += ns * 1e-9
        prog = {n: ns for n, ns in parts.items()
                if n.startswith(PROGRAM_PREFIX)}
        name = (max(prog, key=prog.get) if prog
                else _overlap_label(bench, g0, g1))
        labelled.append((name, (g1 - g0) * 1e-9))
    if spans:  # without program spans trace.reduce's labels stand as they are
        labelled.sort(key=lambda x: -x[1])
        red["idle_gaps"] = [[n, s] for n, s in labelled[:top]]
    red.update(host_self_s_by_span=self_s, span_count=count,
               idle_s_by_span=dict(idle_by_span),
               device_s_by_program=dict(by_program))
    return red
