"""Graph generators of the benchmark, kept apart from the program's own.

A configuration names its generator and parameters; the generator seed is
fixed in the configuration file, so every run of a cell sees the same
graph and the same compiled shapes.  ``--seed`` draws only the roots.

The generators return the raw undirected edge pairs; :func:`csr` is the
benchmark's own de-duplication into a symmetric CSR, which the reference
uses, so the reference never reads a structure that the program built.
"""
from __future__ import annotations

import numpy as np

__all__ = ["GENERATORS", "build_edges", "csr"]


def rmat_edges(scale: int, edge_factor: int, seed: int, a: float, b: float,
               c: float) -> tuple[int, np.ndarray]:
    """Graph500 Kronecker (R-MAT) edge samples.

    n = 2**scale vertices and edge_factor * n undirected edge samples with
    quadrant probabilities a | b / c | d, then a seeded relabelling of the
    vertices so that degree does not follow the id.  Duplicates and
    self-loops are left in the samples; both :func:`csr` and the program
    drop them, as the Graph500 kernels do.
    """
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        r = rng.random(m)
        src_bit = r >= a + b
        dst_bit = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return n, np.stack([perm[src], perm[dst]], axis=1)


def lattice_edges(rows: int, cols: int) -> tuple[int, np.ndarray]:
    """A rows x cols 4-neighbour lattice, ids row-major."""
    idx = np.arange(rows * cols).reshape(rows, cols)
    return rows * cols, np.concatenate([
        np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1),
        np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1),
    ])


GENERATORS = {"rmat": rmat_edges, "lattice": lattice_edges}


def build_edges(spec: dict) -> tuple[int, np.ndarray]:
    """(n, raw edge pairs) for a configuration's ``graph`` entry."""
    params = {k: v for k, v in spec.items() if k != "generator"}
    return GENERATORS[spec["generator"]](**params)


def csr(n: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric CSR (row_ptr int64 [n+1], col int32 [arcs]) of the
    undirected simple graph: self-loops and duplicate pairs dropped."""
    e = np.asarray(edges, np.int64)
    e = e[e[:, 0] != e[:, 1]]
    lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
    key = np.unique(lo * n + hi)
    lo, hi = key // n, key % n
    row = np.concatenate([lo, hi])
    col = np.concatenate([hi, lo])
    order = np.lexsort((col, row))
    row, col = row[order], col[order]
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(row, minlength=n), out=row_ptr[1:])
    return row_ptr, col.astype(np.int32)
