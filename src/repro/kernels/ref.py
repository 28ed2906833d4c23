"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics; the kernels must match them exactly (f32) for
every shape/dtype combination the tests sweep.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "F32_EXACT",
    "matmul_f32",
    "frontier_spmm_ref",
    "dependency_spmm_ref",
    "frontier_partial_ref",
    "dependency_partial_ref",
    "segment_bag_ref",
]

#: Precision of every traversal contraction ``A @ x``, XLA and in-kernel
#: alike.  The 0/1 adjacency is exact in bf16, but the right operand is
#: not: path counts σ and g = (1 + δ + ω) / σ need all 24 bits of f32.
#: On a v5e, default precision rounds them to bf16 in XLA dots and in the
#: BCSR kernel (relative errors of 1e-3), breaking the oracle parity.
F32_EXACT = jax.lax.Precision.HIGHEST


def matmul_f32(a, x):
    """``a @ x`` in f32 at :data:`F32_EXACT` (``a`` cast to f32 first)."""
    return jnp.matmul(a.astype(jnp.float32), x, precision=F32_EXACT)


def frontier_spmm_ref(adjacency, sigma, depth, lvl):
    """One fused forward BFS level (cf. core/engine._forward_level).

    Args:
      adjacency: [n, n] 0/1 (any float dtype).
      sigma:     f32 [n, s] path counts.
      depth:     i32 [n, s] discovery levels (-1 unreached).
      lvl:       i32 scalar — the level being expanded.

    Returns (sigma_out, depth_out).
    """
    frontier = sigma * (depth == lvl - 1)
    contrib = matmul_f32(adjacency, frontier)
    newly = (contrib > 0) & (depth < 0)
    depth_out = jnp.where(newly, lvl, depth)
    sigma_out = sigma + jnp.where(newly, contrib, 0.0)
    return sigma_out, depth_out


def dependency_spmm_ref(adjacency, sigma, depth, delta, omega, lvl):
    """One fused backward dependency level (cf. engine._backward_level).

    Args:
      adjacency: [n, n] 0/1.
      sigma:     f32 [n, s].
      depth:     i32 [n, s].
      delta:     f32 [n, s] running dependencies.
      omega:     f32 [n] 1-degree weights.
      lvl:       i32 scalar.

    Returns delta_out f32 [n, s].
    """
    safe_sigma = jnp.where(sigma > 0, sigma, 1.0)
    g = jnp.where(
        depth == lvl + 1, (1.0 + delta + omega[:, None]) / safe_sigma, 0.0
    )
    t = matmul_f32(adjacency, g)
    return delta + jnp.where(depth == lvl, sigma * t, 0.0)


def frontier_partial_ref(adjacency, sigma, depth, lvl):
    """Pre-fold forward partial for a rectangular adjacency block.

    Args:
      adjacency: [m, k] 0/1 block (any float dtype).
      sigma:     f32 [k, s] gathered path counts (contraction side).
      depth:     i32 [k, s] gathered discovery levels.
      lvl:       i32 scalar.

    Returns t f32 [m, s] = A_block @ (σ ⊙ [d = lvl-1]); the state update
    happens after the cross-device fold (operators.DistributedPallasOperator).
    """
    frontier = sigma * (depth == lvl - 1)
    return matmul_f32(adjacency, frontier)


def dependency_partial_ref(adjacency, sigma, depth, delta, omega, lvl):
    """Pre-fold backward partial for a rectangular adjacency block.

    Args:
      adjacency: [m, k] 0/1 block.
      sigma:     f32 [k, s] (contraction side).
      depth:     i32 [k, s].
      delta:     f32 [k, s].
      omega:     f32 [k].
      lvl:       i32 scalar.

    Returns t f32 [m, s] = A_block @ g with g = (1+δ+ω)/σ on d = lvl+1.
    """
    safe_sigma = jnp.where(sigma > 0, sigma, 1.0)
    g = jnp.where(
        depth == lvl + 1, (1.0 + delta + omega[:, None]) / safe_sigma, 0.0
    )
    return matmul_f32(adjacency, g)


def segment_bag_ref(table, indices, weights=None):
    """EmbeddingBag (sum mode) — the recsys/GNN gather-reduce primitive.

    Args:
      table:   [V, D] embedding rows.
      indices: i32 [B, L] row ids per bag; -1 = padding.
      weights: optional f32 [B, L] per-sample weights.

    Returns f32 [B, D]: out[b] = Σ_l w[b,l] * table[indices[b,l]].
    """
    mask = (indices >= 0).astype(jnp.float32)
    if weights is not None:
        mask = mask * weights
    safe = jnp.maximum(indices, 0)
    gathered = table.astype(jnp.float32)[safe]  # [B, L, D]
    return (gathered * mask[..., None]).sum(axis=1)
