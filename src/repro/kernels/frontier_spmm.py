"""Pallas TPU kernel: fused forward BFS level (frontier SpMM).

The hot loop of MGBC's shortest-path counting is, per level,

    t      = A @ (σ ⊙ [d == lvl-1])
    newly  = (t > 0) ∧ (d < 0)
    d'     = lvl on newly;      σ' = σ + t on newly

A naive XLA lowering materializes the masked frontier ``F = σ⊙mask`` and
the product ``t`` in HBM (two extra n×s round-trips per level — the
dominant *memory-term* cost for small s).  This kernel fuses the mask
into the matmul operand load and the state update into the epilogue, so
per level the only HBM traffic is:  A once (tiled), σ/d once in, σ/d
once out.

Grid = (n/bm, s/bs, n/bk): classic k-innermost matmul tiling with an f32
VMEM accumulator.  The frontier operand tile is recomputed from the
(σ, d) tile on the fly — VMEM-resident, MXU-aligned (block sizes are
multiples of (8, 128) lanes; defaults 128/128/128, shrunk by ops.py for
small inputs).  The adjacency tile may be bf16 (0/1 values are exact) —
halving the A-stream bytes; the accumulator stays f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import F32_EXACT

__all__ = [
    "frontier_spmm_kernel",
    "frontier_spmm_pallas",
    "frontier_partial_kernel",
    "frontier_partial_acc_kernel",
    "frontier_partial_pallas",
]


def frontier_spmm_kernel(
    lvl_ref,  # SMEM-ish (1,1) i32
    a_ref,  # [bm, bk] adjacency tile
    sigma_k_ref,  # [bk, bs] σ tile along contraction dim
    depth_k_ref,  # [bk, bs] d tile along contraction dim
    sigma_io_ref,  # [bm, bs] σ tile being updated
    depth_io_ref,  # [bm, bs] d tile being updated
    sigma_out_ref,  # [bm, bs]
    depth_out_ref,  # [bm, bs]
    acc_ref,  # VMEM scratch [bm, bs] f32
    *,
    k_steps: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lvl = lvl_ref[0, 0]
    frontier = sigma_k_ref[...] * (depth_k_ref[...] == lvl - 1).astype(jnp.float32)
    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        frontier,
        preferred_element_type=jnp.float32,
        precision=F32_EXACT,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        t = acc_ref[...]
        depth = depth_io_ref[...]
        sigma = sigma_io_ref[...]
        newly = (t > 0) & (depth < 0)
        depth_out_ref[...] = jnp.where(newly, lvl, depth)
        sigma_out_ref[...] = sigma + jnp.where(newly, t, 0.0)


def frontier_spmm_pallas(
    adjacency: jnp.ndarray,
    sigma: jnp.ndarray,
    depth: jnp.ndarray,
    lvl: jnp.ndarray,
    *,
    bm: int = 128,
    bk: int = 128,
    bs: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Raw pallas_call wrapper; shapes must already be block-aligned.

    Use :func:`repro.kernels.ops.frontier_spmm` for padding + dispatch.
    """
    n, _ = adjacency.shape
    _, s = sigma.shape
    assert n % bm == 0 and n % bk == 0 and s % bs == 0, (n, s, bm, bk, bs)
    k_steps = n // bk
    grid = (n // bm, s // bs, k_steps)

    lvl_arr = jnp.asarray(lvl, jnp.int32).reshape(1, 1)
    kernel = functools.partial(frontier_spmm_kernel, k_steps=k_steps)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),  # lvl
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # A tile
            pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # σ (contraction)
            pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # d (contraction)
            pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),  # σ (updated)
            pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),  # d (updated)
        ],
        out_specs=[
            pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, s), jnp.float32),
            jax.ShapeDtypeStruct((n, s), jnp.int32),
        ],
        scratch_shapes=[_vmem_scratch(bm, bs)],
        interpret=interpret,
    )(lvl_arr, adjacency, sigma, depth, sigma, depth)


def _vmem_scratch(bm: int, bs: int):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM((bm, bs), jnp.float32)


# --------------------------------------------------------------------------
# Partial (pre-fold) variant for the 2-D distributed engine: the adjacency
# is one device's rectangular block A[rows_i, cols_j], the (σ, d) operands
# are the row-gathered column slice, and the output is the *raw* masked
# product t = A_block @ (σ ⊙ [d = lvl-1]).  The state-update epilogue is
# deferred: it needs the psum_scatter-folded t, so it runs in jnp on the
# owned chunk (see operators.DistributedPallasOperator).  The operand
# fusion — recomputing the frontier tile from (σ, d) in VMEM instead of
# materializing it in HBM — is identical to the square kernel above.
#
# Chunked-operand (ring) mode: the pipelined expand schedule feeds the
# kernel one row-chunk of operands per ring step and threads a running
# [m, s] accumulator through the steps (``acc``).  Seeding the VMEM
# accumulator from the carried tensor keeps the per-step combine inside
# the kernel — no separate [m, s] add round-trips HBM between steps.
# --------------------------------------------------------------------------


def frontier_partial_kernel(
    lvl_ref,  # (1,1) i32
    a_ref,  # [bm, bk] adjacency-block tile
    sigma_k_ref,  # [bk, bs] gathered σ tile (contraction dim)
    depth_k_ref,  # [bk, bs] gathered d tile (contraction dim)
    t_out_ref,  # [bm, bs] partial product
    acc_ref,  # VMEM scratch [bm, bs] f32
    *,
    k_steps: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lvl = lvl_ref[0, 0]
    frontier = sigma_k_ref[...] * (depth_k_ref[...] == lvl - 1).astype(jnp.float32)
    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        frontier,
        preferred_element_type=jnp.float32,
        precision=F32_EXACT,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        t_out_ref[...] = acc_ref[...]


def frontier_partial_acc_kernel(
    lvl_ref,  # (1,1) i32
    a_ref,  # [bm, bk] adjacency-block tile
    sigma_k_ref,  # [bk, bs] chunk σ tile (contraction dim)
    depth_k_ref,  # [bk, bs] chunk d tile (contraction dim)
    t_in_ref,  # [bm, bs] running ring accumulator
    t_out_ref,  # [bm, bs] accumulator + this chunk's product
    acc_ref,  # VMEM scratch [bm, bs] f32
    *,
    k_steps: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = t_in_ref[...]

    lvl = lvl_ref[0, 0]
    frontier = sigma_k_ref[...] * (depth_k_ref[...] == lvl - 1).astype(jnp.float32)
    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        frontier,
        preferred_element_type=jnp.float32,
        precision=F32_EXACT,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        t_out_ref[...] = acc_ref[...]


def frontier_partial_pallas(
    adjacency: jnp.ndarray,  # [m, kdim] rectangular block
    sigma: jnp.ndarray,  # [kdim, s]
    depth: jnp.ndarray,  # [kdim, s]
    lvl: jnp.ndarray,
    *,
    acc: jnp.ndarray | None = None,  # [m, s] ring accumulator (chunked mode)
    bm: int = 128,
    bk: int = 128,
    bs: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call; block-aligned shapes required (see ops.py)."""
    m, kdim = adjacency.shape
    _, s = sigma.shape
    assert m % bm == 0 and kdim % bk == 0 and s % bs == 0, (m, kdim, s, bm, bk, bs)
    k_steps = kdim // bk
    grid = (m // bm, s // bs, k_steps)

    lvl_arr = jnp.asarray(lvl, jnp.int32).reshape(1, 1)
    in_specs = [
        pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),  # lvl
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # A block tile
        pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # σ (contraction)
        pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # d (contraction)
    ]
    args = [lvl_arr, adjacency, sigma, depth]
    if acc is None:
        kernel = functools.partial(frontier_partial_kernel, k_steps=k_steps)
    else:
        kernel = functools.partial(frontier_partial_acc_kernel, k_steps=k_steps)
        in_specs.append(pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)))  # t_in
        args.append(acc)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), jnp.float32),
        scratch_shapes=[_vmem_scratch(bm, bs)],
        interpret=interpret,
    )(*args)
