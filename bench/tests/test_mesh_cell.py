"""The four-chip cell `ycsb-a.zipf099.4chip` as BENCHMARK.json states it:
its configuration file cut to a tiny table runs through the whole harness
on four CPU devices and passes the check, the mesh's exchange left out
fails it, and the mesh roofline share divides by every chip's peak."""
import json
import pathlib
from types import SimpleNamespace

import pytest

from test_checks import exchange_left_out
from tiny import DATA, SEED

BENCH = pathlib.Path(__file__).resolve().parents[1]
CELL = "ycsb-a.zipf099.4chip"


def _spec():
    with open(BENCH.parent / "BENCHMARK.json") as f:
        return json.load(f)


def _run(tmp_path, seconds):
    """The cell over its own configuration file with a 4,096-record table
    (the traffic file's tiny twin under tests/data)."""
    import run

    spec = _spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    with open(BENCH / "configs" / f"{cell['config']}.json") as f:
        cfg = json.load(f)
    cfg["records"] = 4096
    (tmp_path / f"{cell['config']}.json").write_text(json.dumps(cfg))
    return run.run_cell(spec, CELL, SEED, seconds, trace=False,
                        require_chip=False, configs=tmp_path,
                        traffic=DATA / "traffic")


def test_the_cell_states_a_mesh_table_past_one_chip():
    spec = _spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4
    with open(BENCH / "configs" / f"{cell['config']}.json") as f:
        cfg = json.load(f)
    assert cfg["backend"] == "jax_spmd" and cfg["machines"] == 4
    row_bytes = 4 * cfg["record_words"]
    assert cfg["records"] * row_bytes > 16e9  # more than one v5e's HBM
    assert cfg["records"] * cfg["record_words"] < 2 ** 32  # kv.py's index
    names = {m["name"] for m in spec["per_layer"] if CELL in m.get(
        "workloads", [])}
    assert {"work_ratio.4chip", "hbm_roofline_share.4chip"} <= names


@pytest.mark.parametrize("fault", [None, exchange_left_out],
                         ids=["sound", "exchange_left_out"])
def test_tiny_mesh_run_is_checked(fault, tmp_path, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    line = _run(tmp_path, seconds=0.5 if fault is None else 0.3)
    assert line["correct"] == (fault is None), line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_mesh_roofline_share_divides_by_every_chip():
    import importlib.util

    path = BENCH / "metrics" / "hbm_roofline_share.4chip.py"
    spec = importlib.util.spec_from_file_location("mesh_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    busy = {f"/device:TPU:{i}": 0.010 for i in range(4)}
    ctx = SimpleNamespace(
        trace={"busy_s": 0.010, "busy_s_by_device": busy}, calls=2,
        work={"ops": [100, 100], "distinct_writes": [10, 30],
              "row_bytes": 1000},
        peaks={"hbm_bytes_per_s": 1e9})
    # (2*100 + 10 + 2*100 + 30) rows of 1,000 B over 4 GB/s = 110 us of 10 ms
    assert mod.read(ctx) == pytest.approx(1.1)
    ctx.trace = {"busy_s": 0.0, "busy_s_by_device": busy}
    assert mod.read(ctx) is None
