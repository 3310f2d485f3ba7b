"""Public histogram op with backend selection.

`count_ids` is the Phase-1 contention histogram every consumer shares: the
SPMD MoE dispatcher (`core/spmd.py`), the jitted execution backend
(`core/backend.py` via `core/jaxexec.py`), and the hot-chunk electorate.
Unweighted counts dispatch to the Pallas kernel on TPU; weighted counts
(meta-task multiplicities riding aggregated descriptors) take the jnp
scatter path on every backend — the Pallas kernel is a pure counter.
Past `MAX_BINS` the jnp scatter runs on TPU as well.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import routes
from .kernel import histogram
from .ref import histogram_ref

# The kernel compares every id with every bin, so its work and its compile
# time grow with the bin count: AOT compiles for a v5e at N=2^16 ids take
# 1.9 s at 8192 bins, 3.9 s at 16384 and 8.2 s at 32768, and 2^20 bins
# need 72 MiB of the 16 MiB scoped VMEM. Larger histograms take the scatter.
MAX_BINS = 8192


@functools.partial(jax.jit, static_argnames=("num_bins", "backend"))
def count_ids(ids, num_bins: int, *, weights=None, backend: str = "auto"):
    if weights is not None:
        routes.note("histogram", "ref:weighted")
        w = jnp.asarray(weights)
        return jnp.zeros(num_bins, w.dtype).at[
            jnp.asarray(ids).reshape(-1)].add(w.reshape(-1), mode="drop")
    route = routes.pick("histogram", backend, num_bins <= MAX_BINS)
    if route.startswith("ref"):
        return histogram_ref(ids, num_bins)
    return histogram(ids, num_bins, interpret=(route == "interpret"))
