"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a `v5e:2x2` topology that is only described, and refuses what the chip
would refuse — a block whose tiling does not match XLA's layout, a kernel
that needs more VMEM or SMEM than it may use. Interpret mode sees neither.
These tests compile each kernel at the widths the device path runs and at
the size bounds its op dispatches by (`fits_pallas`, `MAX_BINS`), plus the
sharded stage program on the four-chip mesh. Nothing runs: results and
times need the chip (`chip_smoke.py`).

The topology is described inside a module fixture, never while a module is
imported, and the persistent compilation cache is off around these tests
(an entry compiled for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import shardexec
from repro.kernels.histogram.kernel import histogram
from repro.kernels.histogram.ops import MAX_BINS
from repro.kernels.segment_combine.kernel import segment_add
from repro.kernels.segment_combine.ops import MAX_ACC_BYTES, fits_pallas
from repro.kernels.stage_fused import ops as fused_ops
from repro.kernels.stage_fused.kernel import fused_call


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    """Compile `fn` for the described chip; the Pallas kernel must be in
    the program as a TPU custom call."""
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ---------------------------------------------------------------------------
# Phase 1: the contention histogram, up to its bin bound
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,bins", [(65536, 16), (300, 1000),
                                    (65536, MAX_BINS)])
def test_histogram_compiles(one_chip, n, bins):
    _compile(lambda ids: histogram(ids, bins),
             _spec((n,), jnp.int32, one_chip))


# ---------------------------------------------------------------------------
# Phase 4: segment_add, inside and at its VMEM bound
# ---------------------------------------------------------------------------
def _at_bound(width):
    w_pad = -(-width // 128) * 128
    return MAX_ACC_BYTES // (4 * w_pad)


@pytest.mark.parametrize("n,segments,width", [
    (4096, 4096, 16),  # refused before the (1, N) segment row
    (65536, 2048, 32),
    (200, 10, 3),  # one block smaller than the 256-row default
    (65536, _at_bound(16), 16),
    (65536, _at_bound(250), 250),
    (65536, _at_bound(512), 512),
])
def test_segment_add_compiles(one_chip, n, segments, width):
    assert fits_pallas(segments, width)
    _compile(lambda v, s: segment_add(v, s, segments),
             _spec((n, width), jnp.float32, one_chip),
             _spec((n,), jnp.int32, one_chip))


def test_segment_add_bound_is_tight():
    """One more 128-row tile of segments leaves the Pallas route."""
    for width in (16, 250, 512):
        assert not fits_pallas(_at_bound(width) + 128, width)


# ---------------------------------------------------------------------------
# Phases 3+4 fused: the ragged stage kernel at main-path widths and bounds
# ---------------------------------------------------------------------------
def _finish_scale(c, r):
    return r * c[:, :1]


def _fused_specs(sharding, n, nnz, K, w, c=2):
    n_pad = -(-(n + 1) // 8) * 8
    nnz_pad = -(-nnz // 128) * 128
    i32 = jnp.int32
    return (_spec((2 * (n_pad // 8),), i32, sharding),
            _spec((n_pad,), i32, sharding), _spec((n_pad,), i32, sharding),
            _spec((n_pad, 1), i32, sharding), _spec((n_pad, 1), i32, sharding),
            _spec((1, nnz_pad), i32, sharding),
            _spec((1, nnz_pad), i32, sharding),
            _spec((K, w), jnp.float32, sharding),
            _spec((n, c), jnp.float32, sharding))


def _compile_fused(sharding, *, n, nnz, K, w, S, read_op="add",
                   merge_name="add", finish=_finish_scale, np_blocks=4):
    assert fused_ops.fits_pallas(K, w, S, n)
    _compile(lambda *a: fused_call(
        *a, np_blocks=np_blocks, read_op=read_op, finish=finish,
        merge_name=merge_name, combine=True, num_segments=S, w_out=w),
        *_fused_specs(sharding, n, nnz, K, w))


@pytest.mark.parametrize("read_op", fused_ops.FUSED_READ_OPS)
@pytest.mark.parametrize("merge_name", fused_ops.FUSED_MERGES)
def test_fused_stage_compiles_every_op(one_chip, read_op, merge_name):
    _compile_fused(one_chip, n=200, nnz=1500, K=300, w=32, S=100,
                   read_op=read_op, merge_name=merge_name, finish=None)


@pytest.mark.parametrize("n,nnz,K,w,S", [
    (8192, 40000, 32768, 32, 4096),  # the smoke's multi-get: both bounds
    (8192, 40000, 16384, 32, 8192),  # the scoped bound, at a wider combine
    (4096, 30000, 16384, 250, 4096),  # YCSB record width: both bounds
    (65536, 1 << 21, 1024, 32, 1024),  # the most tasks SMEM holds
])
def test_fused_stage_compiles_at_bounds(one_chip, n, nnz, K, w, S):
    _compile_fused(one_chip, n=n, nnz=nnz, K=K, w=w, S=S, np_blocks=64)


@pytest.mark.parametrize("K,w,S,n", [
    (65536, 32, 1024, 8192),  # a 48 MiB table
    (16384, 32, 12288, 8192),  # 16 MiB of onehot and combine blocks
    (1024, 32, 1024, 1 << 17),  # tile bounds past SMEM
])
def test_fused_stage_bound_refuses(K, w, S, n):
    """Shapes that failed to compile are outside `fits_pallas`."""
    assert not fused_ops.fits_pallas(K, w, S, n)


# ---------------------------------------------------------------------------
# the sharded stage program on the four-chip mesh
# ---------------------------------------------------------------------------
def _muladd(contexts, in_vals):
    return {"update": in_vals * contexts[:, 1:2] + contexts[:, 2:3],
            "result": in_vals}


def _sharded_stage(topo, K):
    """The YCSB-A stage of the four-chip cell compiled for a v5e:2x2: K
    records of 250 words over 4 machines (the largest shard homing a few
    thousand more than a quarter, an odd count), 2^16 operations (2^15
    task slots a shard)."""
    P, w, T = 4, 250, 1 << 15
    K_max, words = shardexec.slab_shape((K // P) + 2182, w)
    mesh = jax.sharding.Mesh(np.array(topo.devices[:P]), (shardexec.AXIS,))
    sh = NamedSharding(mesh, PartitionSpec(shardexec.AXIS))
    rep = NamedSharding(mesh, PartitionSpec())
    prog = shardexec.build_stage_program(
        mesh, f=_muladd, fwd_mask=False, ragged=False, merge_name="write",
        combine=True, want_update=False, want_result=True, P=P,
        K_max=K_max, T=T, Np=T, A=1, H=0, w=w, np_dtype=np.float32)
    i32, f32 = jnp.int32, jnp.float32
    per_task = [_spec((P, T), i32, sh) for _ in range(7)]
    slabs = _spec((P, K_max, words), f32, sh)
    specs = (slabs, _spec((P, T, 3), f32, sh),
             _spec((P, T), jnp.bool_, sh), *per_task,
             _spec((P, 1), i32, sh), _spec((P, 1), i32, sh),
             _spec((P, 1), i32, sh), _spec((P, 1, 1), jnp.bool_, sh),
             _spec((1,), i32, rep), _spec((1,), i32, rep),
             _spec((1, w), f32, rep))
    return prog.lower(*specs).compile(), K_max * w * 4


def test_sharded_stage_compiles_on_four_chips(topo):
    """2^24 records (4.2 GB of slab a chip): the stage updates the donated
    slab in place, and beside it needs what the batch needs, whatever the
    table's size."""
    compiled, slab = _sharded_stage(topo, 1 << 24)
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()
    # each chip holds its quarter of the table (rows padded to 256 lanes),
    # not the whole of it
    assert mem.argument_size_in_bytes < 1.1 * slab
    assert mem.alias_size_in_bytes >= slab  # the output slab is the input
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < 1.3 * slab
    # the temporaries do not grow with the table (the compiler picks a
    # different schedule for small slabs, with more of them)
    small, _ = _sharded_stage(topo, 1 << 20)
    assert mem.temp_size_in_bytes <= small.memory_analysis().temp_size_in_bytes
    assert mem.temp_size_in_bytes < 0.1 * slab


@pytest.mark.parametrize("program", ["upload_block", "row_gather"])
def test_slab_programs_compile_on_four_chips(topo, program):
    """The slab's upload writes each block in place, and the write-back's
    row gather reads only the rows asked for, at 2^24 records."""
    P, w = 4, 250
    K_max, wp = shardexec.slab_shape((1 << 22) + 2182, w)
    mesh = jax.sharding.Mesh(np.array(topo.devices[:P]), (shardexec.AXIS,))
    sh = NamedSharding(mesh, PartitionSpec(shardexec.AXIS))
    slabs = _spec((P, K_max, wp), jnp.float32, sh)
    if program == "upload_block":
        _, write = shardexec._block_writer(mesh)
        block = _spec((P, shardexec.UPLOAD_ROWS, wp), jnp.float32, sh)
        mem = write.lower(slabs, block, jax.ShapeDtypeStruct(
            (), jnp.int32)).compile().memory_analysis()
        assert mem.alias_size_in_bytes == mem.output_size_in_bytes
    else:
        rows = _spec((P, 8192), jnp.int32, sh)
        mem = shardexec._row_gather(mesh, w).lower(
            slabs, rows).compile().memory_analysis()
        assert mem.output_size_in_bytes < 8192 * 256 * 4 * 1.01
    assert mem.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("read", ["columns", "rows"])
def test_slab_view_reads_compile_on_four_chips(topo, read):
    """The check's two reads of the resident table through `SlabView` at
    2^24 records (a few columns of every slab row, every word of a few
    rows) need no copy of the slab beside it."""
    P, w, rows = 4, 250, (1 << 22) + 2182
    K_max, wp = shardexec.slab_shape(rows, w)
    mesh = jax.sharding.Mesh(np.array(topo.devices[:P]), (shardexec.AXIS,))
    slabs = _spec((P, K_max, wp), jnp.float32,
                  NamedSharding(mesh, PartitionSpec(shardexec.AXIS)))
    rng = np.random.default_rng(0)
    if read == "columns":
        idx = (slice(None), slice(None), np.sort(rng.choice(w, 4, False)))
    else:
        idx = (rng.integers(0, P, 512), rng.integers(0, rows, 512))
    mem = jax.jit(lambda s: shardexec.SlabView(s, rows, w)[idx]).lower(
        slabs).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 0.15 * K_max * wp * 4
