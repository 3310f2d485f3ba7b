"""Runs of the harness at the tiny sizes of tests/data, on the CPU."""
import json
import pathlib

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 2 ** 31 + 77  # larger than 32 signed bits hold


def run_tiny(workload: str, seed: int = SEED, seconds: float = 0.5,
             control: bool = False) -> dict:
    import run

    with open(DATA / "spec.json") as f:
        spec = json.load(f)
    return run.run_cell(spec, workload, seed, seconds, trace=False,
                        control=control, require_chip=False,
                        configs=DATA / "configs", traffic=DATA / "traffic")
