"""Pallas TPU kernel: fused backward dependency level.

Per level of MGBC's dependency accumulation (checking successors):

    g   = (1 + δ + ω) / σ   on  d == lvl+1   (0 elsewhere)
    t   = A @ g
    δ' += σ ⊙ t             on  d == lvl

As with the forward kernel, the operand ``g`` is recomputed from the
(σ, d, δ, ω) tiles inside the matmul loop instead of being materialized
in HBM, and the δ update is fused into the epilogue.  This mirrors the
paper's "reuse the forward prefix-sum in the backward sweep": the level
structure (d) streams through VMEM once per level with no auxiliary
offset arrays.

Grid and tiling identical to frontier_spmm (ω broadcast along s is an
extra [bk, 1] tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import F32_EXACT

__all__ = [
    "dependency_spmm_kernel",
    "dependency_spmm_pallas",
    "dependency_partial_kernel",
    "dependency_partial_acc_kernel",
    "dependency_partial_pallas",
]


def dependency_spmm_kernel(
    lvl_ref,  # (1,1) i32
    a_ref,  # [bm, bk]
    sigma_k_ref,  # [bk, bs]
    depth_k_ref,  # [bk, bs]
    delta_k_ref,  # [bk, bs]
    omega_k_ref,  # [bk, 1]
    sigma_io_ref,  # [bm, bs]
    depth_io_ref,  # [bm, bs]
    delta_io_ref,  # [bm, bs]
    delta_out_ref,  # [bm, bs]
    acc_ref,  # VMEM [bm, bs] f32
    *,
    k_steps: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lvl = lvl_ref[0, 0]
    sigma_k = sigma_k_ref[...]
    safe_sigma = jnp.where(sigma_k > 0, sigma_k, 1.0)
    g = jnp.where(
        depth_k_ref[...] == lvl + 1,
        (1.0 + delta_k_ref[...] + omega_k_ref[...]) / safe_sigma,
        0.0,
    )
    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        g,
        preferred_element_type=jnp.float32,
        precision=F32_EXACT,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        t = acc_ref[...]
        keep = depth_io_ref[...] == lvl
        delta_out_ref[...] = delta_io_ref[...] + jnp.where(
            keep, sigma_io_ref[...] * t, 0.0
        )


def dependency_spmm_pallas(
    adjacency: jnp.ndarray,
    sigma: jnp.ndarray,
    depth: jnp.ndarray,
    delta: jnp.ndarray,
    omega: jnp.ndarray,
    lvl: jnp.ndarray,
    *,
    bm: int = 128,
    bk: int = 128,
    bs: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call; block-aligned shapes required (see ops.py)."""
    n, _ = adjacency.shape
    _, s = sigma.shape
    assert n % bm == 0 and n % bk == 0 and s % bs == 0, (n, s, bm, bk, bs)
    k_steps = n // bk
    grid = (n // bm, s // bs, k_steps)

    lvl_arr = jnp.asarray(lvl, jnp.int32).reshape(1, 1)
    omega_col = omega.astype(jnp.float32).reshape(n, 1)
    kernel = functools.partial(dependency_spmm_kernel, k_steps=k_steps)

    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),  # lvl
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # A
            pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # σ (contraction)
            pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # d (contraction)
            pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # δ (contraction)
            pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)),  # ω
            pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),  # σ (update)
            pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),  # d (update)
            pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),  # δ (update)
        ],
        out_specs=pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bs), jnp.float32)],
        interpret=interpret,
    )(lvl_arr, adjacency, sigma, depth, delta, omega_col, sigma, depth, delta)


# --------------------------------------------------------------------------
# Partial (pre-fold) variant for the 2-D distributed engine: rectangular
# adjacency block, gathered (σ, d, δ, ω) operands along the contraction
# dim, raw output t = A_block @ g with the g recompute fused in VMEM.
# The δ-update epilogue is deferred past the psum_scatter fold (see
# operators.DistributedPallasOperator and frontier_spmm.py).
#
# Chunked-operand (ring) mode: ``acc`` threads the running [m, s] partial
# through the ring steps of the pipelined expand — the VMEM accumulator
# is seeded from the carried tensor instead of zeros (see the frontier
# kernel for the schedule).
# --------------------------------------------------------------------------


def dependency_partial_kernel(
    lvl_ref,  # (1,1) i32
    a_ref,  # [bm, bk] adjacency-block tile
    sigma_k_ref,  # [bk, bs]
    depth_k_ref,  # [bk, bs]
    delta_k_ref,  # [bk, bs]
    omega_k_ref,  # [bk, 1]
    t_out_ref,  # [bm, bs]
    acc_ref,  # VMEM [bm, bs] f32
    *,
    k_steps: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lvl = lvl_ref[0, 0]
    sigma_k = sigma_k_ref[...]
    safe_sigma = jnp.where(sigma_k > 0, sigma_k, 1.0)
    g = jnp.where(
        depth_k_ref[...] == lvl + 1,
        (1.0 + delta_k_ref[...] + omega_k_ref[...]) / safe_sigma,
        0.0,
    )
    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        g,
        preferred_element_type=jnp.float32,
        precision=F32_EXACT,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        t_out_ref[...] = acc_ref[...]


def dependency_partial_acc_kernel(
    lvl_ref,  # (1,1) i32
    a_ref,  # [bm, bk] adjacency-block tile
    sigma_k_ref,  # [bk, bs]
    depth_k_ref,  # [bk, bs]
    delta_k_ref,  # [bk, bs]
    omega_k_ref,  # [bk, 1]
    t_in_ref,  # [bm, bs] running ring accumulator
    t_out_ref,  # [bm, bs]
    acc_ref,  # VMEM [bm, bs] f32
    *,
    k_steps: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = t_in_ref[...]

    lvl = lvl_ref[0, 0]
    sigma_k = sigma_k_ref[...]
    safe_sigma = jnp.where(sigma_k > 0, sigma_k, 1.0)
    g = jnp.where(
        depth_k_ref[...] == lvl + 1,
        (1.0 + delta_k_ref[...] + omega_k_ref[...]) / safe_sigma,
        0.0,
    )
    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        g,
        preferred_element_type=jnp.float32,
        precision=F32_EXACT,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        t_out_ref[...] = acc_ref[...]


def dependency_partial_pallas(
    adjacency: jnp.ndarray,  # [m, kdim]
    sigma: jnp.ndarray,  # [kdim, s]
    depth: jnp.ndarray,  # [kdim, s]
    delta: jnp.ndarray,  # [kdim, s]
    omega: jnp.ndarray,  # [kdim]
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
    omega_col = omega.astype(jnp.float32).reshape(kdim, 1)
    in_specs = [
        pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),  # lvl
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # A block tile
        pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # σ (contraction)
        pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # d (contraction)
        pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),  # δ (contraction)
        pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)),  # ω
    ]
    args = [lvl_arr, adjacency, sigma, depth, delta, omega_col]
    if acc is None:
        kernel = functools.partial(dependency_partial_kernel, k_steps=k_steps)
    else:
        kernel = functools.partial(dependency_partial_acc_kernel, k_steps=k_steps)
        in_specs.append(pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)))  # t_in
        args.append(acc)

    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bs), jnp.float32)],
        interpret=interpret,
    )(*args)
