"""Production meshes. A FUNCTION, not a module-level constant — importing
this module must never touch jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init;
tests and benches see 1 device)."""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_mesh(shape, axes, **kw):
    """`jax.make_mesh` with every axis in Auto sharding mode."""
    return jax.make_mesh(tuple(shape), tuple(axes), **_auto(len(axes)), **kw)


def abstract_mesh(shape, axes):
    """A device-free `AbstractMesh` of the same shape and axis modes."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes),
                                     **_auto(len(axes)))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips ("data", "model").
    Multi-pod: 2×16×16 = 512 chips ("pod", "data", "model") — the pod axis
    carries pure DP (gradient all-reduce over DCI); FSDP/TP stay within the
    pod's ICI domain."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    return make_mesh((data, model), ("data", "model"))
