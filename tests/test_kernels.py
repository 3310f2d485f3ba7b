"""Per-kernel validation through one shared family harness.

Every kernel family is described by a `Family` spec — geometry list, case
builder, and three runners: `kernel` (interpret-mode Pallas, explicit tile
sizes crossing block boundaries), `fallback` (the jnp path the public op
dispatches to off-TPU), and `ref` (the oracle). One parametrized test then
asserts BOTH paths match the oracle for every (family, geometry) cell, so
adding a kernel family means adding a spec row, not a test class.

mamba_scan's off-TPU fallback IS the interpret-mode kernel (its "ref"
branch is a numpy oracle that cannot run under jit), so its fallback runner
pins the public-op dispatch plumbing rather than a second numeric path.

Family-specific edge cases that don't fit the shared shape (bf16 io, empty
expert groups, all-one-bin skew, model-layer composition) keep their own
tests below the harness.
"""
import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.kernel import flash_decode
from repro.kernels.flash_decode.ops import decode_attention
from repro.kernels.flash_decode.ref import decode_attention_ref
from repro.kernels.histogram.kernel import histogram
from repro.kernels.histogram.ops import count_ids
from repro.kernels.histogram.ref import histogram_ref
from repro.kernels.mamba_scan.kernel import ssd_scan
from repro.kernels.mamba_scan.ops import mamba_ssd
from repro.kernels.mamba_scan.ref import ssd_scan_ref
from repro.kernels.moe_gemm.ops import grouped_gemm
from repro.kernels.moe_gemm.ref import grouped_gemm_ref
from repro.kernels.segment_combine.kernel import segment_add
from repro.kernels.segment_combine.ops import combine_add
from repro.kernels.segment_combine.ref import segment_add_ref
from repro.kernels.stage_fused.ops import fused_stage
from repro.kernels.stage_fused.ref import fused_stage_ref


# ---------------------------------------------------------------------------
# the family table
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    geoms: Tuple          # geometry descriptors, one harness cell each
    make: Callable        # (rng, geom) -> case dict
    kernel: Callable      # case -> array   (interpret-mode Pallas)
    fallback: Callable    # case -> array   (the off-TPU jnp dispatch)
    ref: Callable         # case -> array   (oracle)
    atol: float = 1e-5
    rtol: float = 1e-5
    exact: bool = False


# --- flash_attention -------------------------------------------------------
def _fa_case(rng, geom):
    S, H, KV, hd, bq, bk, causal = geom
    B = 2
    return dict(
        q=jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32),
        k=jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32),
        v=jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32),
        causal=causal, bq=bq, bk=bk)


FLASH = Family(
    name="flash_attention",
    geoms=tuple((S, H, KV, hd, bq, bk, causal)
                for (S, H, KV, hd, bq, bk) in [
                    (128, 4, 4, 64, 64, 64),    # MHA
                    (256, 8, 2, 64, 128, 64),   # GQA 4:1
                    (128, 4, 1, 128, 64, 128),  # MQA
                    (64, 2, 2, 32, 64, 32)]     # tiny head_dim
                for causal in (True, False)),
    make=_fa_case,
    kernel=lambda c: flash_attention(c["q"], c["k"], c["v"],
                                     causal=c["causal"], block_q=c["bq"],
                                     block_k=c["bk"], interpret=True),
    fallback=lambda c: attention(c["q"], c["k"], c["v"], causal=c["causal"],
                                 backend="ref"),
    ref=lambda c: attention_ref(c["q"], c["k"], c["v"], causal=c["causal"]),
    atol=2e-5, rtol=2e-5)


# --- flash_decode ----------------------------------------------------------
def _fd_case(rng, geom):
    B, T, KV, G, hd, length, bt = geom
    return dict(
        q=jnp.asarray(rng.normal(size=(B, KV * G, hd)), jnp.float32),
        k=jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32),
        v=jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32),
        length=length, bt=bt)


DECODE = Family(
    name="flash_decode",
    geoms=((2, 128, 2, 4, 64, 100, 64),   # GQA, ragged valid prefix
           (1, 256, 1, 8, 64, 256, 128),  # MQA, full cache
           (2, 64, 4, 1, 32, 1, 64)),     # MHA, single valid token
    make=_fd_case,
    kernel=lambda c: flash_decode(c["q"], c["k"], c["v"], c["length"],
                                  block_t=c["bt"], interpret=True),
    fallback=lambda c: decode_attention(c["q"], c["k"], c["v"], c["length"],
                                        backend="ref"),
    ref=lambda c: decode_attention_ref(c["q"], c["k"], c["v"], c["length"]),
    atol=2e-5, rtol=2e-5)


# --- histogram -------------------------------------------------------------
def _hist_case(rng, geom):
    E, N = geom
    return dict(ids=jnp.asarray(rng.integers(0, E, size=N), jnp.int32), E=E)


HIST = Family(
    name="histogram",
    geoms=((300, 4000), (1, 1), (7, 257), (16, 1024)),
    make=_hist_case,
    kernel=lambda c: histogram(c["ids"], c["E"], block_n=256, interpret=True),
    fallback=lambda c: count_ids(c["ids"], c["E"], backend="ref"),
    ref=lambda c: histogram_ref(c["ids"], c["E"]),
    exact=True)


# --- moe grouped gemm ------------------------------------------------------
def _moe_case(rng, geom):
    G, M, K, N = geom
    cuts = np.sort(rng.integers(0, M + 1, size=G - 1))
    sizes = np.diff(np.r_[0, cuts, M]).astype(np.int32)
    return dict(
        x=jnp.asarray(rng.normal(size=(M, K)), jnp.float32),
        w=jnp.asarray(rng.normal(size=(G, K, N)) * 0.1, jnp.float32),
        gs=jnp.asarray(sizes), K=K, N=N)


def _moe_run(c, backend):
    return grouped_gemm(c["x"], c["w"], c["gs"], block_m=16,
                        block_n=min(c["N"], 128), block_k=min(c["K"], 64),
                        backend=backend)


MOE = Family(
    name="moe_gemm",
    geoms=((4, 96, 32, 64), (1, 1, 64, 128), (6, 150, 128, 256),
           (3, 17, 32, 64)),  # ragged M far off the block grid
    make=_moe_case,
    kernel=lambda c: _moe_run(c, "interpret"),
    fallback=lambda c: _moe_run(c, "ref"),
    ref=lambda c: grouped_gemm_ref(c["x"], c["w"], c["gs"]),
    atol=2e-4, rtol=2e-4)


# --- segment combine -------------------------------------------------------
def _seg_case(rng, geom):
    V, N, W = geom
    # segment ids deliberately overrun [0, V): rows >= V must drop
    return dict(
        vals=jnp.asarray(rng.normal(size=(N, W)), jnp.float32),
        seg=jnp.asarray(rng.integers(0, V + 2, size=N), jnp.int32), V=V)


SEG = Family(
    name="segment_combine",
    geoms=((200, 2000, 3), (1, 1, 1), (13, 511, 8), (127, 129, 1)),
    make=_seg_case,
    kernel=lambda c: segment_add(c["vals"], c["seg"], c["V"], block_n=128,
                                 interpret=True),
    fallback=lambda c: combine_add(c["vals"], c["seg"], c["V"],
                                   backend="ref"),
    ref=lambda c: segment_add_ref(c["vals"], c["seg"], c["V"]),
    atol=1e-3, rtol=1e-3)


# --- mamba SSD scan --------------------------------------------------------
def _mamba_case(rng, geom):
    S, nh, hd, ds, chunk = geom
    B = 2
    return dict(
        x=jnp.asarray(rng.normal(size=(B, S, nh, hd)), jnp.float32),
        dt=jnp.asarray(rng.uniform(0.01, 0.3, size=(B, S, nh)), jnp.float32),
        A=jnp.asarray(-rng.uniform(0.3, 2.0, size=(nh,)), jnp.float32),
        Bc=jnp.asarray(rng.normal(size=(B, S, ds)), jnp.float32),
        Cc=jnp.asarray(rng.normal(size=(B, S, ds)), jnp.float32),
        chunk=chunk)


MAMBA = Family(
    name="mamba_scan",
    geoms=((32, 2, 8, 8, 16), (64, 3, 16, 8, 16), (128, 1, 32, 16, 32)),
    make=_mamba_case,
    kernel=lambda c: ssd_scan(c["x"], c["dt"], c["A"], c["Bc"], c["Cc"],
                              chunk=c["chunk"], interpret=True),
    fallback=lambda c: mamba_ssd(c["x"], c["dt"], c["A"], c["Bc"], c["Cc"],
                                 chunk=c["chunk"], backend="interpret"),
    ref=lambda c: ssd_scan_ref(c["x"], c["dt"], c["A"], c["Bc"], c["Cc"]),
    atol=1e-3, rtol=1e-3)


# --- fused ragged stage ----------------------------------------------------
def _fused_case(rng, geom):
    n, read_op = geom
    K, w, S = 23, 3, 4
    arity = rng.integers(0, 7, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(arity, out=indptr[1:])
    return dict(
        values=rng.normal(size=(K, w)),
        indptr=indptr,
        indices=rng.integers(0, K, int(indptr[-1])),
        pair_task=np.repeat(np.arange(n), arity),
        ctx=rng.normal(size=(n, 2)),
        seg=rng.integers(0, S + 1, n).astype(np.int32),
        order=rng.permutation(n).astype(np.int32),
        S=S, read_op=read_op)


def _fused_run(c, backend):
    upd, comb = fused_stage(
        c["values"], c["indptr"], c["indices"], c["pair_task"], c["ctx"],
        c["seg"], c["order"], num_segments=c["S"], read_op=c["read_op"],
        merge_name="add", backend=backend)
    return jnp.concatenate([jnp.asarray(upd), jnp.asarray(comb)])


def _fused_oracle(c):
    upd, comb = fused_stage_ref(
        c["values"], c["indptr"], c["indices"], c["pair_task"], c["ctx"],
        c["seg"], c["order"], num_segments=c["S"], read_op=c["read_op"],
        merge_name="add")
    return jnp.concatenate([jnp.asarray(upd), jnp.asarray(comb)])


FUSED = Family(
    name="stage_fused",
    geoms=((1, "add"), (9, "min"), (24, "max"), (13, "first")),
    make=_fused_case,
    kernel=lambda c: _fused_run(c, "interpret"),
    fallback=lambda c: _fused_run(c, "ref"),
    ref=_fused_oracle)


FAMILIES = (FLASH, DECODE, HIST, MOE, SEG, MAMBA, FUSED)
CELLS = [(fam, gi) for fam in FAMILIES for gi in range(len(fam.geoms))]


# ---------------------------------------------------------------------------
# the harness: every family x geometry x {interpret kernel, jnp fallback}
# ---------------------------------------------------------------------------
def _check(fam, geom, path, seed=0):
    case = fam.make(np.random.default_rng(seed), geom)
    got = np.asarray(getattr(fam, path)(case))
    want = np.asarray(fam.ref(case))
    if fam.exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, atol=fam.atol, rtol=fam.rtol)


@pytest.mark.parametrize("path", ["kernel", "fallback"])
@pytest.mark.parametrize("fam,gi", CELLS,
                         ids=[f"{f.name}-g{i}" for f, i in CELLS])
def test_family_matches_ref(fam, gi, path):
    _check(fam, fam.geoms[gi], path)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), fi=st.integers(0, len(FAMILIES) - 1),
       path=st.sampled_from(["kernel", "fallback"]))
def test_property_sweep(seed, fi, path):
    fam = FAMILIES[fi]
    _check(fam, fam.geoms[seed % len(fam.geoms)], path, seed=seed)


# ---------------------------------------------------------------------------
# edge cases outside the shared shape
# ---------------------------------------------------------------------------
def test_flash_bf16_io():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


def test_moe_empty_groups():
    x = jnp.ones((8, 32))
    w = jnp.ones((4, 32, 16))
    gs = jnp.array([0, 8, 0, 0], jnp.int32)
    for backend in ("interpret", "ref"):
        y = grouped_gemm(x, w, gs, block_m=8, block_n=16, block_k=32,
                         backend=backend)
        np.testing.assert_allclose(np.asarray(y), 32.0 * np.ones((8, 16)))


def test_histogram_skewed_all_one_bin():
    ids = jnp.zeros(10_000, jnp.int32)
    for got in (histogram(ids, 16, interpret=True),
                count_ids(ids, 16, backend="ref")):
        assert int(got[0]) == 10_000 and int(got[1:].sum()) == 0


def test_mamba_matches_model_layer():
    """Kernel output composes to the same result as the model's chunked
    SSD implementation (minus the D·x skip handled outside)."""
    from repro.configs import get_reduced
    from repro.models.mamba import _dims, _split_proj, _causal_conv

    cfg = get_reduced("zamba2-1.2b")
    from repro.models.mamba import init_mamba
    params = init_mamba(jax.random.PRNGKey(0), cfg, jnp.float32)
    s, d_in, nh, conv_ch = _dims(cfg)
    B, S = 2, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    z, xbc, dt = _split_proj(params, cfg, x)
    xbc, _ = _causal_conv(xbc, params["conv_w"], params["conv_b"], None)
    xs = xbc[..., :d_in].reshape(B, S, nh, s.head_dim)
    Bc = xbc[..., d_in:d_in + s.d_state]
    Cc = xbc[..., d_in + s.d_state:]
    A = -jnp.exp(params["A_log"])
    y_kernel = ssd_scan(xs.astype(jnp.float32), dt, A, Bc, Cc,
                        chunk=8, interpret=True)
    y_ref = ssd_scan_ref(xs, dt, A, Bc, Cc)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("jit", [False, True])
def test_split3_is_lossless(jit):
    """The three bfloat16 parts the gather/segment-sum kernels contract
    sum back to the f32 value exactly, across magnitudes."""
    from repro.kernels.onehot import split3

    rng = np.random.default_rng(0)
    x = (rng.standard_normal(4096)
         * 10.0 ** rng.integers(-20, 20, 4096)).astype(np.float32)
    parts = (jax.jit(split3) if jit else split3)(jnp.asarray(x))
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    total = sum(np.asarray(p, np.float32) for p in parts)
    np.testing.assert_array_equal(total, x)


def _dot_operand_dtypes(jaxpr):
    """Operand dtypes of every dot_general in `jaxpr` and its sub-jaxprs
    (kernel bodies, pl.when branches)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(tuple(str(v.aval.dtype) for v in eqn.invars))
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _dot_operand_dtypes(inner)
    return found


@pytest.mark.parametrize("kernel", ["segment_add", "stage_fused"])
def test_kernel_matmuls_take_bf16_parts(kernel):
    """On a TPU an f32 x f32 matmul at default precision rounds both
    operands to bfloat16 (interpret mode never shows it): every MXU
    contraction in the onehot kernels must take bf16 operands, the values
    split exactly by `split3`."""
    from repro.kernels.stage_fused.kernel import fused_stage_pallas

    rng = np.random.default_rng(1)
    if kernel == "segment_add":
        fn = lambda v, s: segment_add(v, s, 40, interpret=True)  # noqa: E731
        args = (jnp.asarray(rng.standard_normal((300, 16)), jnp.float32),
                jnp.asarray(rng.integers(0, 40, 300), jnp.int32))
    else:
        indptr = np.arange(0, 121, 4)
        fn = lambda v, c: fused_stage_pallas(  # noqa: E731
            v, indptr, rng.integers(0, 50, 120), np.repeat(np.arange(30), 4),
            c, np.arange(30) % 7, np.arange(30), num_segments=7,
            read_op="add", interpret=True)
        args = (jnp.asarray(rng.standard_normal((50, 8)), jnp.float32),
                jnp.zeros((30, 1), jnp.float32))
    dots = _dot_operand_dtypes(jax.make_jaxpr(fn)(*args).jaxpr)
    assert dots and all(d == ("bfloat16", "bfloat16") for d in dots), dots
