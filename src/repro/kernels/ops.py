"""jit'd public wrappers for the Pallas kernels.

Each op:
  * pads inputs to block multiples (MXU lanes: multiples of (8, 128)),
  * dispatches to the Pallas kernel: compiled on TPU, interpreted on the
    CPU backend (where tests validate kernel semantics), and refused on
    any other backend (:func:`resolve_interpret`),
  * falls back to the pure-jnp reference when ``use_pallas=False``
    (XLA path; useful for A/B perf comparison and as the grad path).

Block sizes adapt downward for small inputs so tests can sweep tiny
shapes; production shapes use the 128-aligned defaults.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.blocked_spmm import (
    dependency_sparse_pallas,
    frontier_sparse_pallas,
    tiles_to_dense,
)
from repro.kernels.dependency_spmm import (
    dependency_partial_pallas,
    dependency_spmm_pallas,
)
from repro.kernels.frontier_spmm import frontier_partial_pallas, frontier_spmm_pallas
from repro.kernels.segment_bag import segment_bag_pallas

__all__ = [
    "frontier_spmm",
    "dependency_spmm",
    "frontier_spmm_partial",
    "dependency_spmm_partial",
    "frontier_spmm_sparse",
    "dependency_spmm_sparse",
    "segment_bag",
    "checksum_append",
    "checksum_residual",
    "bucket_index",
    "resolve_interpret",
]


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas interpret mode for the default backend, unless given.

    TPU runs the kernels compiled and the CPU backend interprets them.
    Any other backend raises instead of interpreting in silence: the
    kernels are Mosaic TPU programs, and an interpreted kernel on an
    accelerator would report the interpreter's speed as the device's.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels are TPU programs; backend {backend!r} can "
        "neither compile them nor is it the CPU backend that interprets "
        "them (pass interpret=True explicitly to interpret anyway)"
    )


def _pad_to(x: jnp.ndarray, axis: int, multiple: int, fill=0):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _pick_block(dim: int, preferred: int, lane: int) -> int:
    """Largest lane-aligned block ≤ preferred covering dim efficiently."""
    if dim >= preferred:
        return preferred
    return max(lane, ((dim + lane - 1) // lane) * lane)


def _square_geometry(n: int, s: int, bm: int, bk: int, bs: int):
    """Block sizes + padded n for the square (fused-epilogue) kernels:
    n must be a multiple of lcm(bm, bk) so the update and contraction
    tilings agree."""
    bm, bk, bs = _pick_block(n, bm, 8), _pick_block(n, bk, 8), _pick_block(s, bs, 128)
    npad = n + (-n) % (bm * bk // math.gcd(bm, bk))
    return bm, bk, bs, npad


def _rect_geometry(m: int, kdim: int, s: int, bm: int, bk: int, bs: int):
    """Block sizes for the rectangular partial kernels (the shared
    _pick_block plumbing of the frontier/dependency partial wrappers)."""
    return _pick_block(m, bm, 8), _pick_block(kdim, bk, 8), _pick_block(s, bs, 128)


def _pad_cols(bs: int, *pairs):
    """Pad each (array, fill) pair along axis 1 to a multiple of ``bs``
    (the shared operand plumbing of the two blocked-sparse wrappers);
    ``None`` arrays pass through (the optional ring ``acc``)."""
    return tuple(
        None if a is None else _pad_to(a, 1, bs, fill=f) for a, f in pairs
    )


def bucket_index(dist: jnp.ndarray, delta: float, unreached: int = -1) -> jnp.ndarray:
    """i32 bucket ids ``⌊d/Δ⌋`` of a tentative-distance array.

    Unreached vertices carry ``+inf`` distance; casting ``inf/Δ`` to int
    is undefined, so the floor is computed on a 0-substituted copy and
    masked back to ``unreached`` (the bucketed traversal's analogue of
    the level array's -1).  Shared by the weighted round's 2-degree
    depth derivation and its max-bucket reduction (core/driver.py).
    """
    delta_w = jnp.float32(delta)
    finite = jnp.isfinite(dist)
    safe = jnp.where(finite, dist, 0.0)
    return jnp.where(
        finite, jnp.floor(safe / delta_w).astype(jnp.int32), jnp.int32(unreached)
    )


def checksum_append(x: jnp.ndarray) -> jnp.ndarray:
    """Append the ABFT ones-checksum lane to a batched [n, s] operand.

    The extra column is the row-wise sum of the real lanes, so after any
    linear map ``t = A @ x`` (including the distributed expand / ring /
    fold pipeline — all_gather, per-block partials and psum_scatter are
    linear per column) the output's last column must equal the sum of
    its real columns.  The lane rides the existing s-axis padding
    machinery of the SpMM wrappers; :func:`checksum_residual` verifies
    the invariant on the product.
    """
    return jnp.concatenate([x, x.sum(axis=1, keepdims=True)], axis=1)


def checksum_residual(t: jnp.ndarray) -> jnp.ndarray:
    """Relative ABFT residual of a checksum-extended SpMM product.

    ``t`` is [n, s+1] with the ones-checksum lane last.  Returns the f32
    scalar ``max_i |t[i, -1] - Σ_j t[i, j]| / (1 + Σ_j |t[i, j]|)`` —
    ~1e-6 for a healthy f32 reduction, orders of magnitude larger when a
    flipped bit or a bad partial fold broke the column-sum invariant.
    """
    real = t[:, :-1]
    resid = jnp.abs(t[:, -1] - real.sum(axis=1))
    scale = 1.0 + jnp.abs(real).sum(axis=1)
    return jnp.max(resid / scale).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret", "bm", "bk", "bs"))
def frontier_spmm(
    adjacency,
    sigma,
    depth,
    lvl,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
    bm: int = 128,
    bk: int = 128,
    bs: int = 128,
):
    """Fused forward BFS level. See kernels/frontier_spmm.py."""
    if not use_pallas:
        return ref.frontier_spmm_ref(adjacency, sigma, depth, lvl)
    interpret = resolve_interpret(interpret)
    n, s = sigma.shape
    bm, bk, bs, npad = _square_geometry(n, s, bm, bk, bs)
    a = jnp.pad(adjacency, ((0, npad - n), (0, npad - n))) if npad != n else adjacency
    sg = _pad_to(_pad_to(sigma, 0, npad), 1, bs)
    dp = _pad_to(_pad_to(depth, 0, npad, fill=-1), 1, bs, fill=-1)
    sigma_out, depth_out = frontier_spmm_pallas(
        a, sg, dp, lvl, bm=bm, bk=bk, bs=bs, interpret=interpret
    )
    return sigma_out[:n, :s], depth_out[:n, :s]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret", "bm", "bk", "bs"))
def dependency_spmm(
    adjacency,
    sigma,
    depth,
    delta,
    omega,
    lvl,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
    bm: int = 128,
    bk: int = 128,
    bs: int = 128,
):
    """Fused backward dependency level. See kernels/dependency_spmm.py."""
    if not use_pallas:
        return ref.dependency_spmm_ref(adjacency, sigma, depth, delta, omega, lvl)
    interpret = resolve_interpret(interpret)
    n, s = sigma.shape
    bm, bk, bs, npad = _square_geometry(n, s, bm, bk, bs)
    a = jnp.pad(adjacency, ((0, npad - n), (0, npad - n))) if npad != n else adjacency
    sg = _pad_to(_pad_to(sigma, 0, npad), 1, bs)
    dp = _pad_to(_pad_to(depth, 0, npad, fill=-1), 1, bs, fill=-1)
    dl = _pad_to(_pad_to(delta, 0, npad), 1, bs)
    om = _pad_to(omega, 0, npad)
    out = dependency_spmm_pallas(
        a, sg, dp, dl, om, lvl, bm=bm, bk=bk, bs=bs, interpret=interpret
    )
    return out[:n, :s]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret", "bm", "bk", "bs"))
def frontier_spmm_partial(
    adjacency,
    sigma,
    depth,
    lvl,
    *,
    acc=None,
    use_pallas: bool = True,
    interpret: bool | None = None,
    bm: int = 128,
    bk: int = 128,
    bs: int = 128,
):
    """Pre-fold forward partial on a rectangular adjacency block.

    ``adjacency`` is [m, k] (one device's A[rows_i, cols_j]); ``sigma``
    and ``depth`` are the row-gathered [k, s] operands.  Returns the raw
    t = A_block @ (σ ⊙ [d = lvl-1]) f32 [m, s] — callers fold the C
    partials with psum_scatter and apply the state update afterwards.

    Chunked-operand (ring) mode: with ``acc`` (f32 [m, s]) the operands
    are one row-chunk of the gathered slice and the result is
    ``acc + A_chunk @ frontier_chunk`` — the running combine of the
    pipelined expand schedule, fused into the kernel's accumulator init.
    See kernels/frontier_spmm.py (partial variants).
    """
    if not use_pallas:
        t = ref.frontier_partial_ref(adjacency, sigma, depth, lvl)
        return t if acc is None else acc + t
    interpret = resolve_interpret(interpret)
    m, kdim = adjacency.shape
    _, s = sigma.shape
    bm, bk, bs = _rect_geometry(m, kdim, s, bm, bk, bs)
    a = _pad_to(_pad_to(adjacency, 0, bm), 1, bk)
    sg = _pad_to(_pad_to(sigma, 0, bk), 1, bs)
    dp = _pad_to(_pad_to(depth, 0, bk, fill=-1), 1, bs, fill=-1)
    ac = None if acc is None else _pad_to(_pad_to(acc, 0, bm), 1, bs)
    t = frontier_partial_pallas(
        a, sg, dp, lvl, acc=ac, bm=bm, bk=bk, bs=bs, interpret=interpret
    )
    return t[:m, :s]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret", "bm", "bk", "bs"))
def dependency_spmm_partial(
    adjacency,
    sigma,
    depth,
    delta,
    omega,
    lvl,
    *,
    acc=None,
    use_pallas: bool = True,
    interpret: bool | None = None,
    bm: int = 128,
    bk: int = 128,
    bs: int = 128,
):
    """Pre-fold backward partial on a rectangular adjacency block.

    Operands are the row-gathered [k, s] (σ, d, δ) and [k] ω; the g
    recompute is fused into the block matmul.  Returns t = A_block @ g
    f32 [m, s].  With ``acc`` (f32 [m, s]) the operands are one row-chunk
    and the result is ``acc + A_chunk @ g_chunk`` — the pipelined-expand
    running combine.  See kernels/dependency_spmm.py (partial variants).
    """
    if not use_pallas:
        t = ref.dependency_partial_ref(adjacency, sigma, depth, delta, omega, lvl)
        return t if acc is None else acc + t
    interpret = resolve_interpret(interpret)
    m, kdim = adjacency.shape
    _, s = sigma.shape
    bm, bk, bs = _rect_geometry(m, kdim, s, bm, bk, bs)
    a = _pad_to(_pad_to(adjacency, 0, bm), 1, bk)
    sg = _pad_to(_pad_to(sigma, 0, bk), 1, bs)
    dp = _pad_to(_pad_to(depth, 0, bk, fill=-1), 1, bs, fill=-1)
    dl = _pad_to(_pad_to(delta, 0, bk), 1, bs)
    om = _pad_to(omega, 0, bk)
    ac = None if acc is None else _pad_to(_pad_to(acc, 0, bm), 1, bs)
    t = dependency_partial_pallas(
        a, sg, dp, dl, om, lvl, acc=ac, bm=bm, bk=bk, bs=bs, interpret=interpret
    )
    return t[:m, :s]


@functools.partial(jax.jit, static_argnames=("m", "use_pallas", "interpret", "bs"))
def frontier_spmm_sparse(
    tiles,
    tile_rows,
    tile_cols,
    sigma,
    depth,
    lvl,
    *,
    m: int,
    acc=None,
    tile_offset=0,
    use_pallas: bool = True,
    interpret: bool | None = None,
    bs: int = 128,
):
    """Blocked-sparse pre-fold forward partial (BCSR tile list).

    ``tile_rows`` / ``tile_cols`` (i32 [T]) index one device's stored
    nonzero tiles (row-sorted, row-complete — build with
    :meth:`repro.graphs.partition.TwoDPartition.blocked_sparse`), which
    are ``tiles[tile_offset : tile_offset + T]`` of the [≥ T, bm, bk]
    ``tiles``; ``sigma``/``depth`` are the gathered [kdim, s] operands.
    Returns the raw t = A_block @ (σ ⊙ [d = lvl-1]) f32 [m, s], touching
    only the stored tiles — A-stream bytes O(T · bm · bk) instead of
    O(m · kdim).

    Modes mirror :func:`frontier_spmm_partial`: full (barrier schedule,
    operands = the whole gathered slice), per-ring-chunk partial
    (operands = one [chunk, s] chunk, tiles = the whole ring layout,
    ``tile_offset`` = that ring slot's first tile — a traced scalar the
    kernel's tile index map adds, so no slot is copied), and
    chunked-``acc`` (the running ring combine seeds the kernel's VMEM
    accumulator).  ``m`` is static: the fold-partial row count
    (C·chunk), not derivable from the tile list.
    """
    if not use_pallas:
        a = tiles_to_dense(tiles, tile_rows, tile_cols, m, sigma.shape[0], tile_offset)
        t = ref.frontier_partial_ref(a, sigma, depth, lvl)
        return t if acc is None else acc + t
    interpret = resolve_interpret(interpret)
    s = sigma.shape[1]
    bs = _pick_block(s, bs, 128)
    sg, dp, ac = _pad_cols(bs, (sigma, 0), (depth, -1), (acc, 0))
    t = frontier_sparse_pallas(
        tiles, tile_rows, tile_cols, sg, dp, lvl, m=m, acc=ac,
        tile_offset=tile_offset, bs=bs, interpret=interpret,
    )
    return t[:, :s]


@functools.partial(jax.jit, static_argnames=("m", "use_pallas", "interpret", "bs"))
def dependency_spmm_sparse(
    tiles,
    tile_rows,
    tile_cols,
    sigma,
    depth,
    delta,
    omega,
    lvl,
    *,
    m: int,
    acc=None,
    tile_offset=0,
    use_pallas: bool = True,
    interpret: bool | None = None,
    bs: int = 128,
):
    """Blocked-sparse pre-fold backward partial (BCSR tile list).

    Operands are the gathered [kdim, s] (σ, d, δ) and [kdim] ω; the g
    recompute is fused per stored tile.  Returns t = A_block @ g f32
    [m, s].  Same full / ring-chunk / chunked-``acc`` modes and
    ``tile_offset`` as :func:`frontier_spmm_sparse`.
    """
    if not use_pallas:
        a = tiles_to_dense(tiles, tile_rows, tile_cols, m, sigma.shape[0], tile_offset)
        t = ref.dependency_partial_ref(a, sigma, depth, delta, omega, lvl)
        return t if acc is None else acc + t
    interpret = resolve_interpret(interpret)
    s = sigma.shape[1]
    bs = _pick_block(s, bs, 128)
    sg, dp, dl, ac = _pad_cols(bs, (sigma, 0), (depth, -1), (delta, 0), (acc, 0))
    t = dependency_sparse_pallas(
        tiles, tile_rows, tile_cols, sg, dp, dl, omega, lvl, m=m, acc=ac,
        tile_offset=tile_offset, bs=bs, interpret=interpret,
    )
    return t[:, :s]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret", "bd"))
def segment_bag(
    table,
    indices,
    weights=None,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
    bd: int = 128,
):
    """EmbeddingBag(sum). See kernels/segment_bag.py."""
    if not use_pallas:
        return ref.segment_bag_ref(table, indices, weights)
    interpret = resolve_interpret(interpret)
    V, D = table.shape
    bd = _pick_block(D, bd, 128)
    t = _pad_to(table, 1, bd)
    out = segment_bag_pallas(t, indices, weights, bd=bd, interpret=interpret)
    return out[:, :D]
