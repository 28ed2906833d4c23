"""Pallas TPU kernels for MGBC's compute hot spots + the EmbeddingBag.

Each kernel ships three layers:
  <name>.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling
  ops.py    — jit'd wrapper (padding, dispatch; interpreted on the CPU backend)
  ref.py    — pure-jnp oracle (the semantics; tests assert allclose)
"""
from repro.kernels.ops import (
    dependency_spmm,
    dependency_spmm_sparse,
    frontier_spmm,
    frontier_spmm_sparse,
    segment_bag,
)

__all__ = [
    "frontier_spmm",
    "dependency_spmm",
    "frontier_spmm_sparse",
    "dependency_spmm_sparse",
    "segment_bag",
]
