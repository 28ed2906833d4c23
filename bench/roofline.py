"""Operations and bytes that the SpMM kernels' work needs, and the least
time a chip could take for it.

Counted from the graph and the batch, never from the tile layout the
program shipped: at each level the forward kernel multiplies the arcs
held on a chip by the [cols, s] frontier operand into a [rows, s]
product, the backward kernel by the dependency operand.  On an R x C
mesh a chip's block has rows = n / R and cols = n / C (on one chip both
are n).

* operations: 2 · arcs · s (a multiply and an add per arc and column);
* bytes: one 4-byte index per arc, plus the float32/int32 operands the
  algorithm must read and the result it writes — forward: σ and depth
  [cols, s] read, the [rows, s] product written; backward: δ, σ and depth
  [cols, s] and ω [cols] read, the product written.

The least time is the larger of operations over the chip's bf16 peak and
bytes over its HBM bandwidth (``bench/peaks.json``).
"""
from __future__ import annotations

import dataclasses

__all__ = ["KernelCost", "forward_cost", "backward_cost", "least_seconds"]

WORD = 4  # bytes of an index, a float32 and an int32


@dataclasses.dataclass(frozen=True)
class KernelCost:
    ops: float
    bytes: float


def forward_cost(arcs: float, rows: int, s: int, cols: int | None = None) -> KernelCost:
    cols = rows if cols is None else cols
    return KernelCost(ops=2.0 * arcs * s, bytes=WORD * (arcs + 2 * cols * s + rows * s))


def backward_cost(arcs: float, rows: int, s: int, cols: int | None = None) -> KernelCost:
    cols = rows if cols is None else cols
    return KernelCost(ops=2.0 * arcs * s,
                      bytes=WORD * (arcs + 3 * cols * s + cols + rows * s))


def least_seconds(cost: KernelCost, peaks: dict) -> tuple[float, str]:
    """(least seconds, which bound: ``"compute"`` or ``"memory"``)."""
    t_ops = cost.ops / float(peaks["bf16_flops_per_s"])
    t_bytes = cost.bytes / float(peaks["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops > t_bytes else (t_bytes, "memory")
