"""Compile the main path's kernels and rounds for a described TPU v5e.

Nothing here runs: each test lowers and compiles one program for a
``v5e:2x2`` topology that the TPU compiler describes without a chip, and
asserts that the compiled program carries the Mosaic kernel
(``tpu_custom_call``) — i.e. that the kernel compiles for the chip at
the widths the chip run uses, which interpret mode cannot show
(unaligned slices, VMEM overuse, oversized programs).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the test workers
must all collect the same tests.  Code that asks ``jax.default_backend()``
sees the CPU here, so every kernel is given ``interpret=False``.
"""
from __future__ import annotations

import functools
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import ops

N = 32768  # the single-chip cell's vertex count (R-MAT SCALE 15)
S = 128  # one round at the MXU width
TILES = 2048  # stored BCSR tiles per cell (a shape; compile cost is flat in it)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a program compiled for a described chip cannot be read back from the
    # persistent cache here; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("adj_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel", ["frontier", "dependency"])
def test_square_kernels_compile(one_chip, kernel, adj_dtype):
    a = _sds((N, N), adj_dtype, one_chip)
    st = _sds((N, S), jnp.float32, one_chip)
    dp = _sds((N, S), jnp.int32, one_chip)
    lvl = _sds((), jnp.int32, one_chip)
    if kernel == "frontier":
        lowered = ops.frontier_spmm.lower(a, st, dp, lvl, interpret=False)
    else:
        om = _sds((N,), jnp.float32, one_chip)
        lowered = ops.dependency_spmm.lower(a, st, dp, st, om, lvl, interpret=False)
    _assert_kernel(lowered)


@pytest.mark.parametrize("kernel", ["frontier", "dependency"])
def test_dense_partial_kernels_compile_with_acc(one_chip, kernel):
    # one ring step of a 2x2 SCALE 16 cell: [C·chunk, chunk] block slice
    m, k = 32768, 16384
    a = _sds((m, k), jnp.float32, one_chip)
    st = _sds((k, S), jnp.float32, one_chip)
    dp = _sds((k, S), jnp.int32, one_chip)
    acc = _sds((m, S), jnp.float32, one_chip)
    lvl = _sds((), jnp.int32, one_chip)
    if kernel == "frontier":
        fn = functools.partial(ops.frontier_spmm_partial, interpret=False)
        lowered = jax.jit(fn).lower(a, st, dp, lvl, acc=acc)
    else:
        om = _sds((k,), jnp.float32, one_chip)
        fn = functools.partial(ops.dependency_spmm_partial, interpret=False)
        lowered = jax.jit(fn).lower(a, st, dp, st, om, lvl, acc=acc)
    _assert_kernel(lowered)


@pytest.mark.parametrize("kernel", ["frontier", "dependency"])
def test_bcsr_kernels_compile(one_chip, kernel):
    m = kdim = N
    tiles = _sds((TILES, 128, 128), jnp.float32, one_chip)
    idx = _sds((TILES,), jnp.int32, one_chip)
    st = _sds((kdim, S), jnp.float32, one_chip)
    dp = _sds((kdim, S), jnp.int32, one_chip)
    lvl = _sds((), jnp.int32, one_chip)
    if kernel == "frontier":
        fn = functools.partial(ops.frontier_spmm_sparse, m=m, interpret=False)
        lowered = jax.jit(fn).lower(tiles, idx, idx, st, dp, lvl)
    else:
        om = _sds((kdim,), jnp.float32, one_chip)
        fn = functools.partial(ops.dependency_spmm_sparse, m=m, interpret=False)
        lowered = jax.jit(fn).lower(tiles, idx, idx, st, dp, st, om, lvl)
    _assert_kernel(lowered)


def test_single_device_pallas_round_compiles(one_chip):
    from repro.core.bc import make_round_fn
    from repro.core.operators import PallasDenseOperator

    def round_fn(adjacency, sources, derived, omega):
        op_fn = lambda: PallasDenseOperator(adjacency, interpret=False)  # noqa: E731
        return make_round_fn(op_fn, N)(sources, derived, omega)

    lowered = jax.jit(round_fn).lower(
        _sds((N, N), jnp.float32, one_chip),
        _sds((S,), jnp.int32, one_chip),
        _sds((0, 3), jnp.int32, one_chip),
        _sds((N,), jnp.float32, one_chip),
    )
    _assert_kernel(lowered)


#: the sends of a compiled program: ``%name = (<operand shape>, ...)
#: collective-permute-start(...)``
PERMUTE_START = re.compile(
    r"^\s*%\S+ = \((\w+)\[([\d,]*)\][^ ]*, .*? collective-permute-start\(", re.M)


#: the four-chip cell, R-MAT SCALE 16 on a 2x2 mesh: vertices, and the
#: stored tiles of its fullest ring slot
N_2X2, SLOT_2X2 = 65536, 32187


@pytest.fixture(scope="module")
def cell_2x2(topo):
    """The four-chip cell's round under ``expand+fold``, ``h0`` (k = 0),
    128 roots, chunk 16,384, compiled at the cell's tiles per ring slot:
    (the geometry-only partition, the compiled program)."""
    from repro.core.distributed import make_distributed_round_fn
    from repro.graphs.partition import partition_arcs_2d

    R = C = 2
    empty = np.zeros(0, np.int64)
    part = partition_arcs_2d(empty, empty, N_2X2, R, C)  # geometry only
    mesh = Mesh(np.asarray(topo.devices).reshape(R, C), ("data", "model"))
    round_fn = make_distributed_round_fn(
        part, mesh, engine_kind="pallas_sparse", overlap="expand+fold",
        interpret=False,
    )
    grid = NamedSharding(mesh, P("data", "model"))
    rep = NamedSharding(mesh, P())
    lowered = round_fn.lower(
        _sds((R, C, R, SLOT_2X2, 128, 128), jnp.float32, grid),
        _sds((R, C, R, SLOT_2X2), jnp.int32, grid),
        _sds((R, C, R, SLOT_2X2), jnp.int32, grid),
        _sds((part.n_pad,), jnp.float32, NamedSharding(mesh, P(("model", "data")))),
        _sds((1, S), jnp.int32, rep),
        _sds((1, 0, 3), jnp.int32, rep),
    )
    return part, lowered.compile()


def test_2x2_pallas_sparse_expand_fold_round_compiles(cell_2x2):
    """The kernels are compiled, and the ring hops are the ones the entry's
    ``exchange_bytes`` prices: (σ, d) and the fold forward, (σ, d, δ, ω)
    and the fold backward."""
    from repro.core.distributed import exchange_bytes

    part, compiled = cell_2x2
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    sends = PERMUTE_START.findall(text)
    assert len(sends) == 3 + 5
    sent = sum(math.prod(map(int, dims.split(","))) * np.dtype(
        {"f32": np.float32, "s32": np.int32}[dtype]).itemsize for dtype, dims in sends)
    assert sent == sum(exchange_bytes(part, "pallas_sparse", S))


def test_2x2_memory_guard_prices_the_compiled_ring_round(cell_2x2):
    """The kernels read each ring step's tile slot in place, so the
    compiled round copies no slot: its temp stays under 64 MiB, no
    instruction produces an f32[SLOT_2X2, 128, 128] slot, and the guard's
    price of the resident ring layout and state covers what a chip holds."""
    from repro.core.distributed import estimate_device_footprint

    part, compiled = cell_2x2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20
    slot = re.compile(rf"= f32\[{SLOT_2X2},128,128\]")
    assert not slot.findall(compiled.as_text())
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    counts = {"stored_tiles_ring": 2 * SLOT_2X2, "bm": 128, "bk": 128}
    foot = estimate_device_footprint(
        part, "pallas_sparse", S, overlap="expand+fold", tile_counts=counts)
    assert held <= foot["total_bytes"] <= 1.05 * held


@pytest.mark.parametrize("k", [0, S // 2], ids=["k0", "k64"])
def test_1x1_pallas_sparse_round_compiles(topo, k):
    """The one-chip cell's round: R-MAT SCALE 15 on a 1x1 mesh, with no
    derived columns (a schedule that claims nothing) or half a batch."""
    from repro.core.distributed import make_distributed_round_fn
    from repro.graphs.partition import partition_arcs_2d

    empty = np.zeros(0, np.int64)
    part = partition_arcs_2d(empty, empty, N, 1, 1)  # geometry only
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    round_fn = make_distributed_round_fn(
        part, mesh, engine_kind="pallas_sparse", interpret=False
    )
    grid = NamedSharding(mesh, P("data", "model"))
    rep = NamedSharding(mesh, P())
    lowered = round_fn.lower(
        _sds((1, 1, TILES, 128, 128), jnp.float32, grid),
        _sds((1, 1, TILES), jnp.int32, grid),
        _sds((1, 1, TILES), jnp.int32, grid),
        _sds((part.n_pad,), jnp.float32, NamedSharding(mesh, P(("model", "data")))),
        _sds((1, S), jnp.int32, rep),
        _sds((1, k, 3), jnp.int32, rep),
    )
    _assert_kernel(lowered)
