"""CPU checks of the benchmark's own code (run: python -m pytest bench/tests).

They run on JAX's CPU backend with four virtual devices, so the mesh cell's
path runs too; the repository's tier-1 suite (testpaths = tests) does not
collect them."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH / "metrics", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
