"""Exact onehot matmuls for the gather and segment-sum kernels.

`histogram`-style kernels gather and segment-sum f32 values as
``onehot @ values`` on the MXU. At its default precision the TPU rounds f32
operands to bfloat16, which puts a 64-pair multi-get sum off its reference
by about 0.25. `Precision.HIGHEST` fixes that at six bf16 passes and more
VMEM than the kernels can spare. A onehot is exact in bfloat16, so three
passes suffice: split the values into three bf16 parts whose f32 sum is
the value (``split3``), and contract the bf16 onehot with each part.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _top16(x: jnp.ndarray) -> jnp.ndarray:
    """`x` with its low 16 bits cleared: an f32 value bfloat16 holds
    exactly. Masking bits, not converting f32 -> bf16 -> f32: the TPU
    compiler may drop such a round trip of converts as excess precision,
    which made `x - hi` zero and the split a plain bf16 rounding."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jax.lax.bitcast_convert_type(bits & jnp.int32(-65536),
                                        jnp.float32)


def split3(x: jnp.ndarray) -> tuple:
    """(hi, mid, lo) bfloat16 parts of f32 `x` with hi + mid + lo == x:
    hi and mid take 8 significant bits each, lo the 8 that are left."""
    hi = _top16(x)
    r = x - hi
    mid = _top16(r)
    lo = r - mid
    return tuple(part.astype(jnp.bfloat16) for part in (hi, mid, lo))


def onehot_dot(onehot: jnp.ndarray, parts, dims, acc=None):
    """``dot_general(onehot, x, dims)`` in f32 for a 0/1 `onehot` and
    ``parts = split3(x)``: one bf16 MXU pass per part. With a VMEM ref
    `acc`, each pass adds into it in place (no result-sized temporaries)
    and nothing is returned."""
    oh = onehot.astype(jnp.bfloat16)
    out = None
    for part in parts:
        d = jax.lax.dot_general(oh, part, (dims, ((), ())),
                                preferred_element_type=jnp.float32)
        if acc is not None:
            acc[...] += d
        else:
            out = d if out is None else out + d
    return out
