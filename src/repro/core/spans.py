"""Named host spans of the orchestrator, on the profiler's own clock.

Every span is a `jax.profiler.TraceAnnotation` named ``tdorch.<name>``: it
costs about a microsecond when no trace is running and, under
``jax.profiler.trace``, lands in the same trace as the device's operations,
so an idle gap of the chip can be attributed to the host step that was
running. Spans sit at layer boundaries only, never inside a jitted function
or a per-element loop, and add no synchronization: a span around a dispatch
times the enqueue, and the wait shows in the span around the blocking fetch.
Arguments are small ints that identify the request (``stage``, ``round``,
``bytes``).

`SPANS` lists every name the program emits, nested as below (a child runs
inside its parent on the same thread):

    kv.batch ⊃ kv.make_batch, stage
    stage ⊃ stage.boundary, phase1, phase2, phase3, phase4
    plan.round ⊃ plan.host, edgemap
    edgemap ⊃ edgemap.gather, edgemap.propagate, edgemap.f,
              edgemap.combine, edgemap.writeback_cost, edgemap.apply
    backend.* inside phase1–phase4, edgemap.combine or plan.host
"""
from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation

PREFIX = "tdorch."

SPANS = (
    # front doors
    "kv.batch", "kv.make_batch",
    # engines
    "stage", "stage.boundary", "phase1", "phase2", "phase3", "phase4",
    "plan.round", "plan.host",
    "edgemap", "edgemap.gather", "edgemap.propagate", "edgemap.f",
    "edgemap.combine", "edgemap.writeback_cost", "edgemap.apply",
    # execution backends
    "backend.prepare", "backend.upload", "backend.dispatch",
    "backend.fetch", "backend.writeback", "backend.route",
)

# jax.named_scope names inside the jitted stage programs (device side)
SCOPES = ("phase1_histogram", "phase2_fetch_a2a", "phase3_gather_lambda",
          "phase4_combine", "phase4_a2a", "phase4_apply")


def span(name: str, **args) -> TraceAnnotation:
    """The context manager of the span ``tdorch.<name>``."""
    return TraceAnnotation(PREFIX + name, **args)


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return run
    return wrap
