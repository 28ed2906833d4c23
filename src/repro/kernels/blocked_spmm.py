"""Pallas TPU kernels: blocked-sparse (BCSR-style) traversal SpMMs.

The 2-D distributed engine's dense-block kernels stream the whole
[C·chunk, R·chunk] adjacency block from HBM every level — O(n_pad²/p)
bytes per device, regardless of sparsity.  RMAT/real-world graphs are
extremely sparse, so the block is mostly zero tiles; these kernels take
the tiled block-compressed layout of
:meth:`repro.graphs.partition.TwoDPartition.blocked_sparse` — only the
nonzero (bm × bk) tiles, stacked as [T, bm, bk] with per-tile row/col
index maps — and iterate *only the stored tiles*, dropping the A-stream
to O(nnz_tiles · bm · bk) bytes per level.

Grid = (s/bs, T) with the tile index minor.  The tile row/col ids are
**scalar-prefetched** (``pltpu.PrefetchScalarGridSpec``): the BlockSpec
index maps read them to DMA the right operand tile ([tile_cols[t]·bk
rows of the gathered operands]) and output tile ([tile_rows[t]·bm rows
of the partial product]) ahead of the kernel body.  A prefetched
``tile_offset`` places the call's T tiles in a longer stored array
(tile t is ``tiles[tile_offset + t]``), so a ring step streams its slot
straight out of the resident [R·Tr, bm, bk] ring layout instead of a
copied slice.  Tiles arrive sorted by output tile-row, so each tile-row
is one consecutive run of grid steps: the f32 VMEM accumulator
initializes at the run's first tile (from zeros, or from the carried
ring accumulator in ``acc`` mode) and flushes to the output block at
the run's last tile.  The layout
guarantees every tile-row holds at least one (possibly all-zero filler)
tile, so every output block is written exactly once per (row, s-block).

All four kernel variants — frontier/dependency × zero-init/carried-acc
— are products of one :func:`make_sparse_kernel` factory: the tile-row
run accumulate is written once, parameterized by the fused operand math
and the accumulator init.

Both kernels are *partial* (pre-fold) forms mirroring the dense
``frontier_partial_pallas`` / ``dependency_partial_pallas``: the operand
fusion (frontier mask / g recompute in VMEM) is identical, the state
update stays deferred past the psum_scatter fold.  The same entry point
serves the full-block barrier schedule (operands = the row-gathered
[R·chunk, s] slice, tiles = the whole block's list, offset 0) and the
ring-pipelined schedule (operands = one [chunk, s] chunk, tiles = the
whole ring layout with that ring slot's offset, ``acc`` = the running
partial carried between hops).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import F32_EXACT

__all__ = [
    "make_sparse_kernel",
    "frontier_sparse_kernel",
    "frontier_sparse_acc_kernel",
    "frontier_sparse_pallas",
    "dependency_sparse_kernel",
    "dependency_sparse_acc_kernel",
    "dependency_sparse_pallas",
    "tiles_to_dense",
]


def tiles_to_dense(
    tiles, tile_rows, tile_cols, m: int, kdim: int, tile_offset=0
) -> jnp.ndarray:
    """Reconstruct the dense [m, kdim] block from a tile list (jnp).

    The list's T = len(tile_rows) tiles are
    ``tiles[tile_offset : tile_offset + T]``, as the kernels read them.
    Reference/debug path only — the kernels never materialize this.
    Filler/padding tiles are all-zero, so scatter-add is exact.
    """
    tiles = jax.lax.dynamic_slice_in_dim(tiles, tile_offset, tile_rows.shape[0], axis=0)
    _, bm, bk = tiles.shape
    grid = jnp.zeros((m // bm, kdim // bk, bm, bk), jnp.float32)
    grid = grid.at[tile_rows, tile_cols].add(tiles.astype(jnp.float32))
    return grid.transpose(0, 2, 1, 3).reshape(m, kdim)


def _row_run_bounds(rows_ref, t, num_tiles: int):
    """(first, last) flags of tile t within its output tile-row run."""
    row = rows_ref[t]
    first = (t == 0) | (rows_ref[jnp.maximum(t - 1, 0)] != row)
    last = (t == num_tiles - 1) | (rows_ref[jnp.minimum(t + 1, num_tiles - 1)] != row)
    return first, last


def _frontier_operand(lvl, sigma_k_ref, depth_k_ref):
    """Fused forward operand: the masked frontier σ ⊙ [d = lvl-1]."""
    return sigma_k_ref[...] * (depth_k_ref[...] == lvl - 1).astype(jnp.float32)


def _dependency_operand(lvl, sigma_k_ref, depth_k_ref, delta_k_ref, omega_k_ref):
    """Fused backward operand: g = (1 + δ + ω) / σ on d = lvl+1."""
    sigma_k = sigma_k_ref[...]
    safe_sigma = jnp.where(sigma_k > 0, sigma_k, 1.0)
    return jnp.where(
        depth_k_ref[...] == lvl + 1,
        (1.0 + delta_k_ref[...] + omega_k_ref[...]) / safe_sigma,
        0.0,
    )


def make_sparse_kernel(operand_fn, *, carried: bool):
    """Kernel factory: ONE copy of the tile-row-run accumulate.

    All four sparse traversal kernels are the same program — initialize
    the VMEM accumulator at a tile-row run's first tile, fold one
    ``A_tile @ operand_tile`` product per grid step, flush at the run's
    last tile — differing only in the fused operand math (``operand_fn``
    builds the [bk, bs] RHS tile from the prefetched level and the
    operand refs) and the accumulator init (``carried=True`` seeds from
    the ring schedule's ``t_in`` partial instead of zeros).  The factory
    keeps that program in one place; the module-level kernel names below
    are its four products.

    Emitted signature (positional refs, matching ``_sparse_call``):
        rows_ref, cols_ref, lvl_ref,  SMEM i32 (scalar prefetch)
        off_ref
        a_ref                         [1, bm, bk] stored tile
        *operand_refs                 [bk, bs]-tiled operands at tile_cols[t]
        [t_in_ref]                    [bm, bs] ring accumulator (carried)
        t_out_ref                     [bm, bs] partial at tile_rows[t]
        acc_ref                       VMEM scratch [bm, bs] f32
    """

    def kernel(rows_ref, cols_ref, lvl_ref, off_ref, a_ref, *refs, num_tiles: int):
        acc_ref, t_out_ref = refs[-1], refs[-2]
        t_in_ref = refs[-3] if carried else None
        operand_refs = refs[: -3 if carried else -2]
        t = pl.program_id(1)
        first, last = _row_run_bounds(rows_ref, t, num_tiles)

        @pl.when(first)
        def _init():
            acc_ref[...] = (
                jnp.zeros_like(acc_ref) if t_in_ref is None else t_in_ref[...]
            )

        rhs = operand_fn(lvl_ref[0], *operand_refs)
        acc_ref[...] += jnp.dot(
            a_ref[0].astype(jnp.float32),
            rhs,
            preferred_element_type=jnp.float32,
            precision=F32_EXACT,
        )

        @pl.when(last)
        def _flush():
            t_out_ref[...] = acc_ref[...]

    return kernel


frontier_sparse_kernel = make_sparse_kernel(_frontier_operand, carried=False)
frontier_sparse_acc_kernel = make_sparse_kernel(_frontier_operand, carried=True)
dependency_sparse_kernel = make_sparse_kernel(_dependency_operand, carried=False)
dependency_sparse_acc_kernel = make_sparse_kernel(_dependency_operand, carried=True)


def _sparse_call(kernel_pair, m, s, bm, bk, bs, num_tiles, operand_specs, args, acc, interpret):
    """Shared pallas_call shell of the two sparse SpMMs.

    ``kernel_pair`` = (zero-init, carried-acc) factory products — the
    module-level names above, so the public kernels ARE what runs.
    ``args`` = (rows, cols, lvl, off, tiles, *operands); the stored tile
    at grid step t is ``tiles[off + t]``, operand tiles index via
    cols_ref, the output (and ``acc`` input) via rows_ref.  ``num_tiles``
    is the length of the row/col id lists, not of ``tiles``.
    """
    out_spec = pl.BlockSpec((bm, bs), lambda j, t, rows, cols, *_: (rows[t], j))
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda j, t, rows, cols, lvl, off: (off[0] + t, 0, 0)),
        *operand_specs,
    ]
    kernel = functools.partial(kernel_pair[acc is not None], num_tiles=num_tiles)
    if acc is not None:
        in_specs.append(out_spec)  # t_in rides the output block index
        args = args + (acc,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # rows, cols, lvl, tile offset
        grid=(s // bs, num_tiles),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((bm, bs), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, s), jnp.float32),
        interpret=interpret,
    )(*args)


def frontier_sparse_pallas(
    tiles: jnp.ndarray,  # [≥ tile_offset + T, bm, bk] stored tiles
    tile_rows: jnp.ndarray,  # i32 [T] (row-sorted, row-complete)
    tile_cols: jnp.ndarray,  # i32 [T]
    sigma: jnp.ndarray,  # [kdim, s] gathered (or ring-chunk) operand
    depth: jnp.ndarray,  # [kdim, s]
    lvl: jnp.ndarray,
    *,
    m: int,  # output rows (C·chunk)
    acc: jnp.ndarray | None = None,  # [m, s] ring accumulator (chunked mode)
    tile_offset: jnp.ndarray | int = 0,  # i32: this call's first tile
    bs: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call; shapes must be tile-aligned (see ops.py)."""
    (num_tiles,), (bm, bk) = tile_rows.shape, tiles.shape[1:]
    kdim, s = sigma.shape
    assert m % bm == 0 and kdim % bk == 0 and s % bs == 0, (m, kdim, s, bm, bk, bs)
    lvl_arr = jnp.asarray(lvl, jnp.int32).reshape(1)
    off_arr = jnp.asarray(tile_offset, jnp.int32).reshape(1)
    operand_specs = [
        pl.BlockSpec((bk, bs), lambda j, t, rows, cols, *_: (cols[t], j)),  # σ
        pl.BlockSpec((bk, bs), lambda j, t, rows, cols, *_: (cols[t], j)),  # d
    ]
    args = (tile_rows, tile_cols, lvl_arr, off_arr, tiles, sigma, depth)
    return _sparse_call(
        (frontier_sparse_kernel, frontier_sparse_acc_kernel),
        m, s, bm, bk, bs, num_tiles, operand_specs, args, acc, interpret,
    )


def dependency_sparse_pallas(
    tiles: jnp.ndarray,  # [≥ tile_offset + T, bm, bk]
    tile_rows: jnp.ndarray,  # i32 [T]
    tile_cols: jnp.ndarray,  # i32 [T]
    sigma: jnp.ndarray,  # [kdim, s]
    depth: jnp.ndarray,  # [kdim, s]
    delta: jnp.ndarray,  # [kdim, s]
    omega: jnp.ndarray,  # [kdim]
    lvl: jnp.ndarray,
    *,
    m: int,
    acc: jnp.ndarray | None = None,
    tile_offset: jnp.ndarray | int = 0,
    bs: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call; shapes must be tile-aligned (see ops.py)."""
    (num_tiles,), (bm, bk) = tile_rows.shape, tiles.shape[1:]
    kdim, s = sigma.shape
    assert m % bm == 0 and kdim % bk == 0 and s % bs == 0, (m, kdim, s, bm, bk, bs)
    lvl_arr = jnp.asarray(lvl, jnp.int32).reshape(1)
    off_arr = jnp.asarray(tile_offset, jnp.int32).reshape(1)
    omega_col = omega.astype(jnp.float32).reshape(kdim, 1)
    operand_specs = [
        pl.BlockSpec((bk, bs), lambda j, t, rows, cols, *_: (cols[t], j)),  # σ
        pl.BlockSpec((bk, bs), lambda j, t, rows, cols, *_: (cols[t], j)),  # d
        pl.BlockSpec((bk, bs), lambda j, t, rows, cols, *_: (cols[t], j)),  # δ
        pl.BlockSpec((bk, 1), lambda j, t, rows, cols, *_: (cols[t], 0)),  # ω
    ]
    args = (tile_rows, tile_cols, lvl_arr, off_arr, tiles, sigma, depth, delta, omega_col)
    return _sparse_call(
        (dependency_sparse_kernel, dependency_sparse_acc_kernel),
        m, s, bm, bk, bs, num_tiles, operand_specs, args, acc, interpret,
    )
