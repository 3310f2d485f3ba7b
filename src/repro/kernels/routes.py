"""Which realization each kernel family was built with.

Every op resolves ``backend="auto"`` through `pick` — a jitted op once per
traced shape, the host-dispatched `stage_fused` once per call:
``pallas`` (the TPU kernel), ``ref:size`` (on TPU, but the shape is past the
kernel's VMEM bound, so the jnp reference runs on the device),
``ref:off-tpu`` (no TPU: the jnp reference on whatever backend runs), or the
explicitly requested route. `traced()` is a running count per
``(kernel, route)``, so a caller can diff it around a phase and show which
routes that phase built. The count is process-wide: the pick happens while
JAX traces an op, where no caller-owned object is at hand, and nothing in
the program reads it back to decide anything.
"""
from __future__ import annotations

import collections
from typing import Dict, Tuple

import jax

_TRACED: collections.Counter = collections.Counter()


def note(kernel: str, route: str) -> str:
    """Record that `kernel` was built with `route`; returns `route`."""
    _TRACED[(kernel, route)] += 1
    return route


def pick(kernel: str, backend: str, fits: bool) -> str:
    """Resolve a kernel op's ``backend`` argument to a route and note it.
    ``"auto"`` takes the Pallas kernel on TPU when the shape `fits` its
    VMEM bound; anything else is taken as asked."""
    if backend == "auto":
        if jax.default_backend() != "tpu":
            backend = "ref:off-tpu"
        else:
            backend = "pallas" if fits else "ref:size"
    return note(kernel, backend)


def traced() -> Dict[Tuple[str, str], int]:
    """Snapshot of the per-(kernel, route) build counts."""
    return dict(_TRACED)
