"""The trace reduction (harness/trace.py): busy union, idle share and gap
labels, on a hand-made trace and on a small trace recorded on a TPU v5e."""
import json
import pathlib

import pytest

from harness import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def ev(plane, name, start, dur, line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def test_hand_made_trace():
    host, d0, d1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
    events = [
        ev(host, "bench.window", 100, 1000, "python"),
        ev(host, "bench.call", 100, 600, "python"),
        ev(host, "bench.sync", 700, 100, "python"),
        ev(host, "bench.record", 800, 300, "python"),
        # device 0: overlapping ops, one op partly before the window
        ev(d0, "gather", 50, 100),
        ev(d0, "gather", 300, 200),
        ev(d0, "scatter", 400, 200),
        ev(d0, "scatter", 750, 50),
        # device 1: one op
        ev(d1, "gather", 200, 100),
    ]
    r = trace.reduce(events)
    assert r["window_s"] == pytest.approx(1000e-9)
    # device 0 busy: [100,150) + [300,600) + [750,800) = 400 ns; device 1: 100
    assert r["busy_s_by_device"][d0] == pytest.approx(400e-9)
    assert r["busy_s"] == pytest.approx(250e-9)
    # device 0 gaps: [150,300) in call, [600,750) call 100 + sync 50,
    # [800,1100) in record
    assert r["idle_gaps"] == [["bench.record", pytest.approx(300e-9)],
                              ["bench.call", pytest.approx(150e-9)],
                              ["bench.call", pytest.approx(150e-9)]]
    ops = dict(r["device_ops"])
    assert ops["gather"] == pytest.approx((50 + 200 + 100) * 1e-9 / 2)


def _sweep_busy(intervals, w0, w1):
    """Busy time by a sweep over start/end points: another way to the union."""
    pts = sorted([(max(s, w0), 1) for s, e in intervals if e > w0 and s < w1]
                 + [(min(e, w1), -1) for s, e in intervals if e > w0 and s < w1])
    busy, depth, last = 0.0, 0, None
    for t, step in pts:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_trace():
    with open(DATA / "trace_ycsb_a.json") as f:
        events = json.load(f)
    r = trace.reduce(events)
    win = next(e for e in events if e["name"] == trace.WINDOW)
    w0, w1 = win["start_ns"], win["start_ns"] + win["dur_ns"]
    dev = [e for e in events if e["plane"].startswith("/device:")]
    assert dev, "the recorded trace holds device operations"
    want = _sweep_busy([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                        for e in dev], w0, w1)
    assert r["busy_s"] == pytest.approx(want * 1e-9, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = r["window_s"] - r["busy_s"]
    assert sum(r["idle_s_by_host_span"].values()) == pytest.approx(idle)
    assert {n for n, _ in r["idle_gaps"]} <= {"bench.call", "bench.sync",
                                              "bench.record", "none"}
    gaps = [s for _, s in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
