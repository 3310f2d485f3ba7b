"""The reduction of the program's spans (harness/spans.py) and the readers
of the metrics over them: on the recorded TPU trace (no program spans) it
adds keys and changes none; on a hand-made trace with nested ``tdorch.*``
spans its self times, idle attribution and gap labels are the hand-computed
ones; each reader gives None where its span or counter is missing."""
import json
import pathlib
from types import SimpleNamespace

import pytest

from harness import spans, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
OLD_KEYS = ("window_s", "busy_s", "busy_s_by_device", "device_ops",
            "idle_gaps", "idle_s_by_host_span")


def ev(plane, name, start, end, line="XLA Ops", **extra):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": end - start, **extra}


HOST, DEV = "/host:CPU", "/device:TPU:0"


def hand_made(program=True):
    """Window [0, 1000) ns; one batch whose phases 3 and 4 run on the
    device in [320, 500) and [650, 700)."""
    out = [
        ev(HOST, "bench.window", 0, 1000, "python"),
        ev(HOST, "bench.call", 0, 800, "python"),
        ev(HOST, "bench.sync", 800, 850, "python"),
        ev(HOST, "bench.record", 850, 1000, "python"),
        ev(DEV, "gather", 320, 500),
        ev(DEV, "scatter", 650, 700),
        ev(DEV, "jit_run_stage_flat(4933918879303770221)", 320, 500,
           spans.MODULES_LINE),
        ev(DEV, "jit_apply_rows(17843302901216438592)", 650, 700,
           spans.MODULES_LINE),
    ]
    if program:
        out += [ev(HOST, "tdorch." + n, s, t, "python", args=a)
                for n, s, t, a in (
                    ("kv.batch", 10, 790, {}),
                    ("stage", 20, 780, {"stage": 0}),
                    ("phase1", 30, 200, {}),
                    ("phase3", 300, 600, {}),
                    ("backend.dispatch", 310, 330, {}),
                    ("backend.fetch", 330, 590, {}),
                    ("phase4", 600, 770, {}))]
    return out


def test_recorded_trace_keys_unchanged():
    with open(DATA / "trace_ycsb_a.json") as f:
        events = json.load(f)
    old, new = trace.reduce(events), spans.reduce(events)
    for key in OLD_KEYS:
        assert new[key] == old[key], key
    assert new["host_self_s_by_span"] == {} and new["span_count"] == {}
    assert sum(new["idle_s_by_span"].values()) == pytest.approx(
        old["window_s"] - old["busy_s"])


def test_program_spans_leave_the_old_numbers():
    old, new = spans.reduce(hand_made(False)), spans.reduce(hand_made())
    for key in OLD_KEYS:
        if key != "idle_gaps":
            assert new[key] == old[key], key
    assert [n for n, _ in old["idle_gaps"]] == [
        "bench.call", "bench.record", "bench.call"]


def test_hand_made_spans():
    r = spans.reduce(hand_made())
    ns = pytest.approx
    assert r["host_self_s_by_span"] == {
        "tdorch.kv.batch": ns(20e-9), "tdorch.stage": ns(120e-9),
        "tdorch.phase1": ns(170e-9), "tdorch.phase3": ns(20e-9),
        "tdorch.backend.dispatch": ns(20e-9),
        "tdorch.backend.fetch": ns(260e-9), "tdorch.phase4": ns(170e-9)}
    assert r["span_count"]["tdorch.stage"] == 1
    # gaps [0,320), [500,650), [700,1000): each idle instant goes to the
    # span that opened last
    assert r["idle_s_by_span"] == {
        "bench.call": ns(20e-9), "tdorch.kv.batch": ns(20e-9),
        "tdorch.stage": ns(120e-9), "tdorch.phase1": ns(170e-9),
        "tdorch.phase3": ns(20e-9), "tdorch.backend.dispatch": ns(10e-9),
        "tdorch.backend.fetch": ns(90e-9), "tdorch.phase4": ns(120e-9),
        "bench.sync": ns(50e-9), "bench.record": ns(150e-9)}
    assert sum(r["idle_s_by_span"].values()) == ns(r["window_s"]
                                                   - r["busy_s"])
    assert r["idle_gaps"] == [["tdorch.phase1", ns(320e-9)],
                              ["tdorch.phase4", ns(300e-9)],
                              ["tdorch.backend.fetch", ns(150e-9)]]
    assert r["device_s_by_program"] == {"jit_run_stage_flat": ns(180e-9),
                                        "jit_apply_rows": ns(50e-9)}


def load_reader(name):
    from run import load_module

    return load_module(METRICS / f"{name}.py")


READERS = {
    # name: (ctx that has what it reads, the value it gives there)
    "engine_host_ms_per_batch.kv": (
        dict(calls=2), 1e3 * (20 + 120 + 170 + 20 + 170) * 1e-9 / 2),
    "backend_host_ms_per_batch.kv": (
        dict(calls=2), 1e3 * (20 + 260) * 1e-9 / 2),
    "host_transfer_mb_per_batch.kv": (
        dict(calls=2, before={"transfer_bytes": 1_000_000},
             after={"transfer_bytes": 5_000_000}), 2.0),
    "edgemap_host_ms_per_round.graph": (
        dict(calls=1, before={"rounds": 0}, after={"rounds": 2},
             by_span={"tdorch.plan.round": 1e-3, "tdorch.edgemap": 2e-3,
                      "tdorch.edgemap.f": 3e-3,
                      "tdorch.backend.fetch": 9.0}), 3.0),
    "host_transfer_mb_per_round.graph": (
        dict(calls=1, before={"rounds": 4, "transfer_bytes": 0},
             after={"rounds": 6, "transfer_bytes": 128_000_000}), 64.0),
}


def ctx_for(calls=1, before=None, after=None, by_span=None):
    red = spans.reduce(hand_made())
    if by_span is not None:
        red["host_self_s_by_span"] = by_span
    return SimpleNamespace(trace=red, before=before or {}, after=after or {},
                           calls=calls)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads(name):
    kw, want = READERS[name]
    assert load_reader(name).read(ctx_for(**kw)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_none_when_missing(name):
    kw, _ = READERS[name]
    reader = load_reader(name)
    # the parent's program: no program spans, no transfer counter
    bare = ctx_for(**{**kw, "before": {"rounds": 0}, "after": {"rounds": 2}})
    bare.trace = spans.reduce(hand_made(False))
    assert reader.read(bare) is None
    bare.trace = trace.reduce(hand_made(False))  # the harness's own keys
    assert reader.read(bare) is None


def test_load_events_keeps_program_spans(tmp_path):
    """On the CPU (no device plane): the harness's spans as trace.py reads
    them, plus the program's with their arguments."""
    import io

    import jax

    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("tdorch.stage", stage=7):
            with jax.profiler.TraceAnnotation("tdorch.phase1"):
                pass
    trace.stop()
    got = spans.load_events(str(tmp_path), io.StringIO())
    old = trace.load_events(str(tmp_path), io.StringIO())
    assert [e for e in got if not e["name"].startswith("tdorch.")] == old
    prog = {e["name"]: e for e in got if e["name"].startswith("tdorch.")}
    assert set(prog) == {"tdorch.stage", "tdorch.phase1"}
    assert prog["tdorch.stage"]["args"] == {"stage": 7}
