"""Distributed MGBC: 2-D decomposition + sub-clustering (paper §3.2-3.3).

Communication structure per traversal level, per sub-cluster (an R×C
grid of devices; see graphs/partition.py for the chunk layout):

  expand (vertical, paper Alg. 2 line 15):
      all_gather(frontier-σ chunk, axis=row)  →  F[cols_j]  on every
      device of grid column j — O(√p) partners.
  local compute (node level):
      * ``engine_kind="sparse"`` — gather F[src_local] + segment_sum
        into dst_local (the TPU replacement for the CUDA active-edge
        kernel);
      * ``engine_kind="pallas"`` / ``"pallas_bf16"`` — the device's dense
        adjacency block on the MXU via the fused frontier/dependency
        SpMM kernels in partial mode (kernels/frontier_spmm.py) — the
        fine-grained dense-block compute the 2-D decomposition is
        designed to feed.
  fold (horizontal, Alg. 2 line 19):
      psum_scatter(partials, axis=col) — sums the C partial
      contributions and delivers each device exactly its owned chunk.

That is the *barrier* schedule (``overlap="none"``): every device idles
through both collectives.  ``overlap="expand"`` replaces the all_gather
with R-1 ``ppermute`` ring steps, accumulating each device's per-chunk
product against the chunk in hand while the next is in flight (paper
Fig. 2 pipelining / collective-matmul decomposition);
``overlap="expand+fold"`` additionally replaces the psum_scatter with a
C-1-step reduce ring, leaving no monolithic collective on the level's
critical path — per level the cost drops from T_comm + T_compute toward
max(T_comm, T_compute).

The traversal itself — level loops, round algebra, host loop — is NOT
implemented here: the shard_map body below constructs a
:class:`repro.core.operators.DistributedOperator` (or its Pallas
dense-block subclass) and runs the same
:func:`repro.core.driver.traversal_round` /
:class:`repro.core.driver.BCDriver` as the single-device path.

With the sparse operator, *all* state stays owner-sharded and only
frontier-σ / g ever travel — the depth test of the edge's far endpoint
is folded into the gathered quantity (one exchange per level; recorded
as a beyond-paper optimization in EXPERIMENTS.md §Perf).  The Pallas
dense-block operator exchanges (σ, d) forward and (σ, d, δ, ω) backward
— the paper's §3.2 exchange set — in return for fusing the mask / g
recompute into the MXU block matmul.

Sub-clustering (paper §3.3): a leading mesh axis carries ``fr`` graph
replicas, each processing different source rounds; BC is additive so the
final merge sums the replica dim (host-side, in the shared driver, so a
straggling/preempted replica's round can be re-issued — and, with
``straggler="steal"|"redeal"``, actively moved between replicas by the
driver's multi-ledger scheduler; see core/driver.py and
distributed/fault_tolerance.py).
"""
from __future__ import annotations

import logging
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.driver import (
    BCDriver,
    DEFAULT_MAX_RETRIES,
    DEFAULT_RETRY_BACKOFF_S,
    normalize_integrity,
    traversal_round,
)
from repro.core.operators import (
    DistributedOperator,
    DistributedPallasHybridOperator,
    DistributedPallasOperator,
    DistributedPallasSparseOperator,
    DistributedWeightedDenseOperator,
    DistributedWeightedOperator,
    auto_delta,
    normalize_overlap,
)
from repro.core.scheduler import Schedule, build_schedule
from repro.core.spans import attach_run, span
from repro.graphs.graph import Graph
from repro.graphs.partition import TwoDPartition, partition_2d
from repro.roofline.model import (
    adjacency_stream_bytes,
    auto_overlap_policy,
    cell_kernel_choice,
    device_hardware,
    device_hbm_footprint,
    exchange_operands,
    sparse_tile_bytes,
)

__all__ = [
    "DIST_ENGINE_KINDS",
    "make_distributed_round_fn",
    "distributed_graph_arrays",
    "graph_host_arrays",
    "put_graph_arrays",
    "distributed_betweenness_centrality",
    "one_degree_reduce_distributed",
    "resolve_overlap",
    "hybrid_cell_choice",
    "level_time_estimates",
    "exchange_bytes",
    "prior_round_seconds",
    "weighted_prior_levels",
    "estimate_device_footprint",
    "check_device_memory",
    "WATCHDOG_SAFETY",
    "WATCHDOG_MIN_DEADLINE_S",
]

logger = logging.getLogger(__name__)

#: ``dispatch_deadline_s="auto"`` resolves to
#: ``max(WATCHDOG_MIN_DEADLINE_S, WATCHDOG_SAFETY × prior_round_seconds)``.
#: The factor is deliberately generous: the roofline prior models steady
#: state, while the first dispatch also pays jit compilation, and a false
#: watchdog trip evicts a healthy replica.
WATCHDOG_SAFETY = 50.0
WATCHDOG_MIN_DEADLINE_S = 60.0

#: block-local compute engines of the distributed path: arc-list
#: gather/segment-sum, fused dense-block Pallas (f32 / bf16 A-stream),
#: the blocked-sparse (BCSR tile list) Pallas engine, or the per-cell
#: dense/BCSR hybrid for skewed meshes.
DIST_ENGINE_KINDS = ("sparse", "pallas", "pallas_bf16", "pallas_sparse", "pallas_hybrid")


def hybrid_cell_choice(
    partition: TwoDPartition,
    bm: int | None = None,
    bk: int | None = None,
    *,
    threshold: float = 1.0,
    tile_counts: dict | None = None,
    measured: tuple[float, float] | None = None,
) -> tuple[np.ndarray, dict]:
    """Resolve the hybrid engine's per-cell dense-vs-BCSR choice.

    Thin wrapper over :func:`repro.roofline.model.cell_kernel_choice`
    feeding it the per-cell stored-tile counts from the partition's
    shared counting pass (pass ``tile_counts`` to reuse a dict already
    computed this resolve; the underlying arc→tile pass is cached either
    way).  The choice is logged — like ``overlap="auto"`` — so runs are
    auditable, and overridable via ``threshold``
    (``--hybrid-threshold``).  ``measured`` is the autotuner's
    (dense_level_s, sparse_level_s) calibration pair: when present the
    break-even compares measured seconds instead of the roofline's bytes
    model.  Returns ``(dense_cells, tile_counts)``.
    """
    counts = tile_counts or partition.blocked_sparse_counts(bm, bk)
    dense_cells = cell_kernel_choice(
        counts["stored_full_cell"],
        R=partition.R,
        C=partition.C,
        chunk=partition.chunk,
        bm=counts["bm"],
        bk=counts["bk"],
        threshold=threshold,
        measured=measured,
    )
    logger.info(
        "hybrid cell choice (threshold %.3g, tile %dx%d, %s): %d dense / "
        "%d sparse cells %s",
        threshold,
        counts["bm"],
        counts["bk"],
        "measured costs" if measured is not None else "roofline bytes",
        int(dense_cells.sum()),
        int(dense_cells.size - dense_cells.sum()),
        dense_cells.astype(int).tolist(),
    )
    return dense_cells, counts


def distributed_graph_arrays(
    partition: TwoDPartition,
    engine_kind: str,
    overlap: str = "none",
    tile: tuple[int, int] | None = None,
    dense_cells: np.ndarray | None = None,
    hybrid_threshold: float = 1.0,
    weights: np.ndarray | None = None,
    *,
    mesh: Mesh | None = None,
    row_axis: str = "data",
    col_axis: str = "model",
) -> tuple[jax.Array, ...]:
    """Device arrays for the graph operands of a distributed round fn.

    The single source of the engine_kind × overlap → operand-layout
    mapping (entry point, benchmarks and tests all lower the same
    layout): sparse uses the flat arc arrays, or the ring-sliced layout
    under a ring overlap policy; the dense Pallas engines use dense
    blocks (bf16 for ``"pallas_bf16"``); ``"pallas_sparse"`` uses the
    blocked tile layout (full tile list, or per-ring-chunk slices under
    a ring policy) — always (tiles, tile_rows, tile_cols);
    ``"pallas_hybrid"`` prepends the dense blocks and appends the i32
    per-cell choice mask — (blocks, tiles, tile_rows, tile_cols,
    dense_cells), each cell's data materialized only in its chosen
    representation (:meth:`TwoDPartition.blocked_hybrid`).  ``tile``
    overrides the blocked-sparse (bm, bk) tile shape (default: the
    largest lane-friendly divisor of ``chunk`` ≤ 128); ``dense_cells``
    overrides the hybrid per-cell choice (default: resolved from the
    roofline threshold via :func:`hybrid_cell_choice`).

    ``weights`` (f32 [num_arcs], graph arc order) swaps the 0/1 operand
    values for edge weights — the bucketed-traversal operand set.  The
    weighted layouts are always the barrier (non-ring) forms regardless
    of ``overlap`` (weighted rounds run barrier collectives; overlap
    only governs replica loop lockstep): sparse grows a third f32
    [R, C, max_arcs] arc-weight array; the dense engines carry f32
    weight blocks even under ``"pallas_bf16"`` (the σ/δ equality masks
    need exact distances, so weights never downcast).

    With ``mesh``, every operand (leading dims [R, C]) is placed straight
    from the host onto its ``(row_axis, col_axis)`` grid shard — each
    device receives only its own cell, replicated over any other mesh
    axis.  Without it the arrays land on the default device (small
    graphs, tests); the round fn's jit then reshards them on every call.
    """
    host = graph_host_arrays(
        partition, engine_kind, overlap, tile=tile, dense_cells=dense_cells,
        hybrid_threshold=hybrid_threshold, weights=weights,
    )
    return put_graph_arrays(host, mesh=mesh, row_axis=row_axis, col_axis=col_axis)


def put_graph_arrays(
    host: tuple[np.ndarray, ...],
    *,
    mesh: Mesh | None = None,
    row_axis: str = "data",
    col_axis: str = "model",
) -> tuple[jax.Array, ...]:
    """The host operands of :func:`graph_host_arrays` on the device(s):
    each onto its ``(row_axis, col_axis)`` grid shard with ``mesh``, else
    onto the default device."""
    if mesh is None:
        return tuple(jnp.asarray(a) for a in host)
    from jax.sharding import NamedSharding

    grid = NamedSharding(mesh, P(row_axis, col_axis))
    return tuple(jax.device_put(a, grid) for a in host)


def graph_host_arrays(
    partition: TwoDPartition,
    engine_kind: str,
    overlap: str = "none",
    tile: tuple[int, int] | None = None,
    dense_cells: np.ndarray | None = None,
    hybrid_threshold: float = 1.0,
    weights: np.ndarray | None = None,
) -> tuple[np.ndarray, ...]:
    """The host (numpy) form of :func:`distributed_graph_arrays`: the
    same operands, in the same order, before any is put on a device."""
    if engine_kind == "sparse":
        if weights is not None:
            return (partition.src_local, partition.dst_local, partition.arc_weights(weights))
        if normalize_overlap(overlap) != "none":
            return partition.ring_arcs()
        return (partition.src_local, partition.dst_local)
    if engine_kind in ("pallas_sparse", "pallas_hybrid"):
        ring = weights is None and normalize_overlap(overlap) != "none"
        bm, bk = tile if tile is not None else (None, None)
        if engine_kind == "pallas_sparse":
            layout = partition.blocked_sparse(bm, bk, ring=ring, weights=weights)
            lead: tuple = ()
        else:
            if dense_cells is None:
                dense_cells, _ = hybrid_cell_choice(
                    partition, bm, bk, threshold=hybrid_threshold
                )
            hybrid = partition.blocked_hybrid(
                bm, bk, dense_cells=dense_cells, ring=ring, weights=weights
            )
            layout = hybrid.sparse
            lead = (hybrid.blocks,)
        if ring:
            tiles = (layout.ring_tiles, layout.ring_tile_rows, layout.ring_tile_cols)
        else:
            tiles = (layout.tiles, layout.tile_rows, layout.tile_cols)
        if engine_kind == "pallas_hybrid":
            return lead + tiles + (dense_cells.astype(np.int32),)
        return tiles
    if weights is not None:
        return (partition.dense_blocks(np.float32, weights=weights),)
    # 0/1 entries are exact in bf16: build the host blocks in the engine's
    # dtype so no f32 copy is shipped or converted
    dt = jnp.bfloat16 if engine_kind == "pallas_bf16" else np.float32
    return (partition.dense_blocks(dt),)


def estimate_device_footprint(
    partition: TwoDPartition,
    engine_kind: str,
    batch_size: int,
    *,
    bm: int | None = None,
    bk: int | None = None,
    overlap: str = "none",
    tile_counts: dict | None = None,
    dense_cells: np.ndarray | None = None,
    hybrid_threshold: float = 1.0,
) -> dict:
    """Per-device adjacency + state HBM bytes for one engine (pre-compile).

    Thin adapter over :func:`repro.roofline.model.device_hbm_footprint`
    filling in the partition-derived quantities; prices what the chosen
    ``overlap`` actually allocates, not a lower bound.  For the
    blocked-sparse engine that is the layout's *stored* tile count —
    true nonzero tiles plus row-complete fillers, pad-to-worst-cell,
    and (under a ring policy) the R per-slot slices
    (:meth:`TwoDPartition.blocked_sparse_counts`, no tile data
    materialized; pass a precomputed ``tile_counts`` to reuse one
    counting-pass dict across resolve/guard — the underlying arc→tile
    pass is cached on the partition either way).  For the hybrid engine
    it is the actually-shipped mixed layout: the dense-block operand
    every device allocates PLUS the sparse tile list masked to the
    sparse-chosen cells (``dense_cells``, default: the roofline choice
    at ``hybrid_threshold``) — shard_map uniformity makes the resident
    adjacency the union of the two representations even though each
    cell only *streams* its chosen one.  For the arc-list engine under
    a ring policy it is the 2·R·max_ring_arcs ring layout
    (:meth:`TwoDPartition.ring_arcs_max`), not the flat arc arrays.
    ``bm``/``bk`` override the default tile shape; pass the same
    ``tile`` the engine will be built with.
    """
    ring = normalize_overlap(overlap) != "none"
    kw: dict = {}
    if engine_kind == "pallas_sparse":
        counts = tile_counts or partition.blocked_sparse_counts(bm, bk)
        kw = dict(
            nnz_tiles=counts["stored_tiles_ring" if ring else "stored_tiles_full"],
            bm=counts["bm"],
            bk=counts["bk"],
        )
    elif engine_kind == "pallas_hybrid":
        if dense_cells is None:
            dense_cells, _ = hybrid_cell_choice(
                partition, bm, bk, threshold=hybrid_threshold,
                tile_counts=tile_counts,
            )
        # accept the i32 form the mask ships in (graph args / JSON records)
        dense_cells = np.asarray(dense_cells, bool)
        counts = partition.blocked_sparse_counts(bm, bk, cells=~dense_cells)
        kw = dict(
            nnz_tiles=counts["stored_tiles_ring" if ring else "stored_tiles_full"],
            bm=counts["bm"],
            bk=counts["bk"],
        )
    elif engine_kind == "sparse":
        max_arcs = int(partition.src_local.shape[-1])
        if ring:
            max_arcs = partition.R * partition.ring_arcs_max()
        kw = dict(max_arcs=max_arcs)
    return device_hbm_footprint(
        engine_kind,
        R=partition.R,
        C=partition.C,
        chunk=partition.chunk,
        batch_size=batch_size,
        **kw,
    )


def check_device_memory(
    partition: TwoDPartition,
    engine_kind: str,
    batch_size: int,
    hbm_limit_bytes: float | None,
    *,
    bm: int | None = None,
    bk: int | None = None,
    overlap: str = "none",
    tile_counts: dict | None = None,
    dense_cells: np.ndarray | None = None,
) -> dict:
    """Fail-fast memory guard: error *before* compiling instead of
    OOMing mid-round, with an actionable suggestion.  Returns the
    footprint record (always computed, so callers can report it).
    ``dense_cells`` is the hybrid engine's resolved per-cell choice, so
    the guard prices the actually-shipped mixed layout."""
    foot = estimate_device_footprint(
        partition, engine_kind, batch_size,
        bm=bm, bk=bk, overlap=overlap, tile_counts=tile_counts,
        dense_cells=dense_cells,
    )
    logger.info(
        "per-device HBM footprint (%s): adjacency %.3f GiB + state %.3f GiB "
        "= %.3f GiB%s",
        engine_kind,
        foot["adjacency_bytes"] / 2**30,
        foot["state_bytes"] / 2**30,
        foot["total_bytes"] / 2**30,
        ""
        if hbm_limit_bytes is None
        else f" (budget {hbm_limit_bytes/2**30:.2f} GiB)",
    )
    if hbm_limit_bytes is not None and foot["total_bytes"] > hbm_limit_bytes:
        suggestions = []
        if engine_kind in ("pallas", "pallas_bf16", "pallas_hybrid"):
            # hybrid ships the dense operand on every device (shard_map
            # uniformity); pure blocked-sparse is the strictly smaller layout
            sparse_foot = estimate_device_footprint(
                partition, "pallas_sparse", batch_size,
                bm=bm, bk=bk, overlap=overlap, tile_counts=tile_counts,
            )
            if sparse_foot["total_bytes"] <= hbm_limit_bytes:
                suggestions.append(
                    "engine_kind='pallas_sparse' (blocked-sparse adjacency: "
                    f"{sparse_foot['total_bytes']/2**30:.2f} GiB/device)"
                )
        suggestions.append("a larger mesh (per-device footprint scales ~1/p)")
        raise MemoryError(
            f"engine_kind={engine_kind!r} needs "
            f"{foot['total_bytes']/2**30:.2f} GiB/device "
            f"(adjacency {foot['adjacency_bytes']/2**30:.2f} GiB + state "
            f"{foot['state_bytes']/2**30:.2f} GiB) but the HBM budget is "
            f"{hbm_limit_bytes/2**30:.2f} GiB; try " + " or ".join(suggestions)
        )
    return foot


def level_time_estimates(
    partition: TwoDPartition,
    engine_kind: str,
    batch_size: int,
    *,
    bm: int | None = None,
    bk: int | None = None,
    tile_counts: dict | None = None,
    dense_cells: np.ndarray | None = None,
    hw=None,
) -> tuple[float, float, float]:
    """Roofline prices of one traversal level: (compute, expand, fold) s.

    The shared pricing behind ``overlap="auto"`` (:func:`resolve_overlap`)
    and the straggler scheduler's EWMA prior
    (:func:`prior_round_seconds`): block compute from the
    engine-dependent FLOPs / A-stream bytes, expand/fold collective
    bytes from the α-β link model.  The hybrid engine is priced per
    cell — each cell streams its *chosen* representation
    (``dense_cells``, default: the roofline choice), and the level waits
    for the slowest cell, so the compute term is the per-cell maximum.
    ``hw`` defaults to the peaks of the device the run is on
    (:func:`repro.roofline.model.device_hardware`).
    """
    R, C, chunk, s = partition.R, partition.C, partition.chunk, batch_size
    hw = device_hardware() if hw is None else hw

    if engine_kind in ("pallas", "pallas_bf16"):
        flops = 2.0 * (C * chunk) * (R * chunk) * s
        a_bytes = adjacency_stream_bytes(engine_kind, R=R, C=C, chunk=chunk)
    elif engine_kind == "pallas_sparse":
        counts = tile_counts or partition.blocked_sparse_counts(bm, bk)
        bm, bk, nnz = counts["bm"], counts["bk"], counts["nnz_max"]
        flops = 2.0 * nnz * bm * bk * s
        a_bytes = adjacency_stream_bytes(
            engine_kind, R=R, C=C, chunk=chunk, nnz_tiles=nnz, bm=bm, bk=bk
        )
    elif engine_kind == "pallas_hybrid":
        counts = tile_counts or partition.blocked_sparse_counts(bm, bk)
        if dense_cells is None:
            dense_cells, _ = hybrid_cell_choice(
                partition, bm, bk, tile_counts=counts
            )
        bm, bk = counts["bm"], counts["bk"]
        dense_flops = 2.0 * (C * chunk) * (R * chunk) * s
        dense_bytes = adjacency_stream_bytes("pallas", R=R, C=C, chunk=chunk)
        stored = np.asarray(counts["stored_full_cell"], np.float64)
        cell_flops = np.where(dense_cells, dense_flops, 2.0 * stored * bm * bk * s)
        cell_bytes = np.where(
            dense_cells, dense_bytes, stored * sparse_tile_bytes(bm, bk)
        )
        cell_s = np.maximum(
            cell_flops / hw.peak_bf16_flops, cell_bytes / hw.hbm_bandwidth
        )
        flops, a_bytes = float(cell_flops.max()), float(cell_bytes.max())
        compute_s = float(cell_s.max())  # the level waits for the slowest cell
    else:  # arc-list: one gather+add per arc per source column
        max_arcs = int(partition.src_local.shape[-1])
        flops = 2.0 * max_arcs * s
        a_bytes = adjacency_stream_bytes(
            engine_kind, R=R, C=C, chunk=chunk, max_arcs=max_arcs
        )
    if engine_kind != "pallas_hybrid":
        compute_s = max(flops / hw.peak_bf16_flops, a_bytes / hw.hbm_bandwidth)
    expand_b, fold_b = _exchange_parts(partition, engine_kind, s)[0]  # forward
    return compute_s, expand_b / hw.ici_link_bandwidth, fold_b / hw.ici_link_bandwidth


def _exchange_parts(partition: TwoDPartition, engine_kind: str, width: int):
    """((expand, fold) forward, (expand, fold) backward) bytes one chip
    sends a level: R-1 hops of its owned exchange set
    (:func:`repro.roofline.model.exchange_operands`, ``width`` columns a
    block) and C-1 f32 [chunk, width] blocks of the fold's partial."""
    R, C, chunk = partition.R, partition.C, partition.chunk
    fold = (C - 1) * chunk * width * 4
    return tuple(
        ((R - 1) * chunk * (blocks * width + vectors) * 4, fold)
        for blocks, vectors in exchange_operands(engine_kind)
    )


def exchange_bytes(partition: TwoDPartition, engine_kind: str, width: int) -> tuple[int, int]:
    """Bytes one chip sends per (forward, backward) level of an unweighted
    round through the expand and the fold, from the shapes and dtypes the
    level ships: ``width`` state columns (batch + derived, plus the
    checksum lane under ``integrity="checksum"``) of the chip's owned
    chunk, all 4-byte (σ, δ f32, d i32, ω f32 [chunk]).

    Every overlap policy sends the same bytes: R-1 ring hops of the owned
    operands or a ring all_gather of them, C-1 reduce-ring hops or a
    psum_scatter; the policy decides only what the transfers overlap.
    0 on a 1x1 mesh.
    """
    fwd, bwd = _exchange_parts(partition, engine_kind, width)
    return sum(fwd), sum(bwd)


#: Nominal level count pricing the straggler prior: forward + backward
#: sweeps of a shallow (RMAT-like) traversal.  The prior only seeds every
#: replica's EWMA symmetrically — it cannot flag a straggler by itself —
#: so the constant's job is order-of-magnitude, not accuracy.
PRIOR_LEVELS = 16


def prior_round_seconds(
    partition: TwoDPartition,
    engine_kind: str,
    batch_size: int,
    overlap: str,
    *,
    bm: int | None = None,
    bk: int | None = None,
    tile_counts: dict | None = None,
    dense_cells: np.ndarray | None = None,
    hw=None,
    measured_level_s: float | None = None,
    prior_levels: int | None = None,
) -> float:
    """Per-round wall estimate — the straggler EWMA's prior.

    With ``measured_level_s`` (the autotuner's measured per-level wall of
    the resolved config) the prior is simply ``measured × PRIOR_LEVELS``
    — a real time scale instead of a modelled one.  Otherwise one level
    is priced under the resolved collective schedule
    (:func:`repro.roofline.model.overlap_step_time` via
    :func:`repro.roofline.model.auto_overlap_policy`'s estimate table) ×
    :data:`PRIOR_LEVELS` nominal levels.  Gives the scheduler a
    before-any-observation time scale (paper-motivated: round wall is
    data-dependent and unknown until traversal).

    ``prior_levels`` overrides the nominal level count — weighted runs
    substitute the expected *bucket* count of the bucketed traversal
    (≈ depth·w̄/Δ), since a round's trip unit is a distance bucket, not
    a BFS level (:func:`weighted_prior_levels`).
    """
    levels = PRIOR_LEVELS if prior_levels is None else int(prior_levels)
    if measured_level_s is not None:
        return float(measured_level_s) * levels
    hw = device_hardware() if hw is None else hw
    compute_s, expand_s, fold_s = level_time_estimates(
        partition, engine_kind, batch_size,
        bm=bm, bk=bk, tile_counts=tile_counts, dense_cells=dense_cells, hw=hw,
    )
    _, estimates = auto_overlap_policy(
        compute_s, expand_s, fold_s, partition.R, partition.C, hw=hw
    )
    return estimates[normalize_overlap(overlap)] * levels


def weighted_prior_levels(w: np.ndarray, delta: float) -> int:
    """Expected bucket count standing in for :data:`PRIOR_LEVELS`.

    A weighted round's trip unit is a width-Δ distance bucket; at the
    nominal :data:`PRIOR_LEVELS` hop depth the traversal spans roughly
    ``PRIOR_LEVELS · w̄`` distance, i.e. ``⌈PRIOR_LEVELS · w̄ / Δ⌉``
    buckets (never less than the unweighted constant — a wide Δ merges
    buckets but each still costs at least a level's collectives).
    """
    w = np.asarray(w, np.float64)
    w_mean = float(w.mean()) if w.size else 1.0
    return max(PRIOR_LEVELS, int(np.ceil(PRIOR_LEVELS * w_mean / float(delta))))


def resolve_overlap(
    overlap: str | None,
    partition: TwoDPartition,
    engine_kind: str,
    batch_size: int,
    *,
    bm: int | None = None,
    bk: int | None = None,
    tile_counts: dict | None = None,
    dense_cells: np.ndarray | None = None,
    hw=None,
    measured: dict | None = None,
) -> str:
    """Resolve ``overlap="auto"`` from measured or roofline level costs.

    Prices one level's block compute (engine-dependent FLOPs/A-stream)
    and expand/fold collective bytes with the α-β link model, then picks
    the schedule :func:`repro.roofline.model.auto_overlap_policy`
    estimates fastest.  ``measured`` (policy -> measured per-level
    seconds from the autotune cache) takes precedence: when any policy
    has a measurement the pick compares measured policies only.  The
    choice is logged (logging INFO + returned); passing an explicit
    policy bypasses this entirely.  ``bm``/``bk``: the blocked-sparse
    tile shape the engine will actually be built with (defaults to the
    partition default), so the estimate prices the real layout;
    ``dense_cells``: the hybrid engine's resolved per-cell choice, for
    the same reason.  ``hw`` defaults to the peaks of the device the run
    is on (:func:`repro.roofline.model.device_hardware`).
    """
    if overlap != "auto":
        return normalize_overlap(overlap)
    hw = device_hardware() if hw is None else hw
    compute_s, expand_s, fold_s = level_time_estimates(
        partition, engine_kind, batch_size,
        bm=bm, bk=bk, tile_counts=tile_counts, dense_cells=dense_cells, hw=hw,
    )
    policy, estimates = auto_overlap_policy(
        compute_s, expand_s, fold_s, partition.R, partition.C, hw=hw,
        measured=measured,
    )
    logger.info(
        "overlap='auto' -> %r for engine %s (%s per-level estimates: %s)",
        policy,
        engine_kind,
        "measured" if measured else "roofline",
        {k: f"{v*1e6:.2f}us" for k, v in estimates.items()},
    )
    return policy


def one_degree_reduce_distributed(
    graph: Graph, mesh: Mesh, axis_name: str | tuple[str, ...] = "data"
) -> tuple[np.ndarray, np.ndarray]:
    """Distributed 1-degree preprocessing (paper Alg. 6, §3.4.1).

    The paper 1-D-partitions edges, sorts by source and scans; the
    data-parallel equivalent shards the arc list over ``axis_name``,
    computes degrees with a local segment-sum + psum, then marks arcs
    incident to a leaf and accumulates ω the same way.  Near-linear
    scaling (paper Fig. 10) follows from the arc shards being independent
    except for two n-sized all-reduces.

    Returns (omega int64 [n], arc_removed bool [m2]) — identical to the
    host-side :func:`repro.core.heuristics.one_degree.one_degree_reduce`.
    """
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    n = graph.n
    src_p, dst_p, m2 = graph.padded_arcs(multiple=p)

    def body(src, dst):
        ones = jnp.ones_like(src, dtype=jnp.float32)
        deg = jax.lax.psum(
            jax.ops.segment_sum(ones, src, num_segments=n + 1), axes
        )
        leaf = deg == 1.0  # sentinel vertex n has huge degree, never a leaf
        removed = leaf[src] | leaf[dst]
        omega = jax.lax.psum(
            jax.ops.segment_sum(leaf[src].astype(jnp.float32), dst, num_segments=n + 1),
            axes,
        )
        return omega[:n], removed

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes), P(axes)),
        out_specs=(P(), P(axes)),
        check_vma=False,
    )
    omega, removed = jax.jit(fn)(jnp.asarray(src_p), jnp.asarray(dst_p))
    return (
        np.asarray(omega, np.int64),
        np.asarray(removed)[:m2],
    )


def _grid_axes(mesh: Mesh, row_axis: str, col_axis: str, replica_axis: str | None):
    R = mesh.shape[row_axis]
    C = mesh.shape[col_axis]
    fr = mesh.shape[replica_axis] if replica_axis is not None else 1
    return R, C, fr


def make_distributed_round_fn(
    partition: TwoDPartition,
    mesh: Mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    replica_axis: str | None = None,
    num_levels: int | None = None,
    fuse_backward_payload: bool = True,
    engine_kind: str = "sparse",
    interpret: bool | None = None,
    overlap: str = "none",
    integrity: str = "off",
    weighted: bool = False,
    delta: float | None = None,
):
    """Build the sub-cluster-parallel, 2-D-distributed round function.

    With ``engine_kind="sparse"`` (arc-list local compute) the returned
    jitted function maps
      (src_local  i32 [R, C, max_arcs]   — sharded (row, col),
       dst_local  i32 [R, C, max_arcs]   — sharded (row, col),
       omega      f32 [n_pad]            — sharded ((col, row)),
       sources    i32 [fr, s]            — sharded (replica),
       derived    i32 [fr, k, 3]         — sharded (replica))
      -> (bc  f32 [fr, n_pad]  — sharded (replica, (col, row)),
          ns  f32 [fr, s+k]    — sharded (replica),
          roots i32 [fr, s+k]  — sharded (replica),
          levels i32 [fr]      — sharded (replica): each replica's own
          traversal depth this round, the straggler scheduler's
          per-round cost signal)

    With ``engine_kind="pallas"`` / ``"pallas_bf16"`` (dense-block MXU
    local compute) the two arc arrays are replaced by one argument:
      (blocks  f32/bf16 [R, C, C·chunk, R·chunk] — sharded (row, col),
       omega, sources, derived)  ->  same outputs.
    Build the blocks with :meth:`TwoDPartition.dense_blocks`.

    With ``engine_kind="pallas_sparse"`` (blocked-sparse BCSR local
    compute) the graph operands are the tile layout of
    :meth:`TwoDPartition.blocked_sparse`:
      (tiles      f32 [R, C, T, bm, bk]  — sharded (row, col),
       tile_rows  i32 [R, C, T],
       tile_cols  i32 [R, C, T],
       omega, sources, derived)  ->  same outputs;
    under a ring overlap policy the three arrays are the per-ring-chunk
    slices ([R, C, R, Tr, ...], ``blocked_sparse(ring=True)``) — same
    arity, one extra slot dim.  Per-device adjacency memory is
    O(nnz_tiles·bm·bk) instead of the dense engines' O(n_pad²/p).

    With ``engine_kind="pallas_hybrid"`` (per-cell dense/BCSR mix) the
    graph operands prepend the dense blocks and append the choice mask:
      (blocks     f32 [R, C, C·chunk, R·chunk] — sharded (row, col),
       tiles/tile_rows/tile_cols — as for ``pallas_sparse``,
       dense_cells i32 [R, C]    — sharded (row, col),
       omega, sources, derived)  ->  same outputs;
    each cell holds data only in its chosen representation
    (:meth:`TwoDPartition.blocked_hybrid`) and dispatches its fused
    kernels through a local ``lax.cond`` on its choice scalar.

    ``fuse_backward_payload`` keeps σ-frontier and g exchanges as a single
    gathered tensor each (the paper's overlap/fusion idea, §3.2 Fig. 2);
    setting it False splits the backward gather into two half-width
    collectives to mimic the paper's unfused σ/d exchange for the
    Fig. 9 benchmark (sparse engine only).

    ``overlap`` selects the collective schedule per
    :data:`repro.core.operators.OVERLAP_POLICIES`: ``"none"`` keeps the
    barrier all_gather → compute → psum_scatter level step; ``"expand"``
    ring-pipelines the gather (ppermute steps interleaved with per-chunk
    block compute); ``"expand+fold"`` additionally turns the fold into a
    reduce ring.  Under a ring policy the sparse engine's two arc
    arguments are the *ring-sliced* layout
    (i32 [R, C, R, max_ring_arcs] from
    :meth:`TwoDPartition.ring_arcs`) instead of the flat arc arrays —
    same arity, per-row-chunk slicing.

    ``integrity`` (:data:`repro.core.driver.INTEGRITY_MODES`) makes each
    round self-verifying: with ``"audit"`` or ``"checksum"`` the output
    grows a fifth slot, f32 [fr, 2] — per replica the max ABFT checksum
    residual over all level steps (``"checksum"`` only; 0 otherwise) and
    the replica's claimed bc-block sum, which the driver cross-checks
    against the delivered block at drain time.  ``"checksum"`` requires
    the fused backward payload: the checksum lane rides the column axis
    through every exchange, and the split σ/d gather would carry it
    through only half the backward operands.

    ``weighted=True`` (with a positive ``delta`` bucket width) swaps the
    level-synchronous round for the bucketed weighted traversal.  The
    operand layouts are the barrier (non-ring) forms from
    :func:`distributed_graph_arrays` with ``weights=``: the sparse
    engine's signature grows a third f32 arc-weight array; the dense
    Pallas engines take one f32 weight-block operand; the BCSR/hybrid
    tile layouts keep their unweighted arity and are densified per
    device cell inside the shard_map body (fused weighted tile kernels
    are the documented follow-up — weighted compute is XLA contractions
    either way).  Collectives run the barrier schedule regardless of
    ``overlap``, which only keeps sub-cluster replicas in bucket-loop
    lockstep (``sync_axes``); ``num_levels`` (static trip counts) and
    ``integrity="checksum"`` (a level-synchronous ABFT lane) are
    rejected.
    """
    R, C, fr = _grid_axes(mesh, row_axis, col_axis, replica_axis)
    if (R, C) != (partition.R, partition.C):
        raise ValueError(
            f"mesh grid {(R, C)} != partition grid {(partition.R, partition.C)}"
        )
    if engine_kind not in DIST_ENGINE_KINDS:
        raise ValueError(f"unknown distributed engine {engine_kind!r}")
    overlap = normalize_overlap(overlap)
    integrity = normalize_integrity(integrity)
    use_pallas = engine_kind != "sparse"  # any fused-kernel engine
    if use_pallas and not fuse_backward_payload:
        raise ValueError("split backward payload is a sparse-engine benchmark mode")
    if integrity == "checksum" and not fuse_backward_payload:
        raise ValueError(
            "integrity='checksum' needs the fused backward payload: the "
            "checksum lane must travel with every exchanged operand"
        )
    if overlap != "none" and not fuse_backward_payload:
        raise ValueError(
            "split backward payload is a barrier-schedule benchmark mode; "
            "it cannot be combined with a ring overlap policy"
        )
    if weighted:
        if delta is None or not (float(delta) > 0):
            raise ValueError(
                f"weighted rounds need a positive bucket width delta, got {delta}"
            )
        if num_levels is not None:
            raise ValueError(
                "num_levels is a static level bound for the level-synchronous "
                "engine; the weighted bucket loop's trip count is data-dependent"
            )
        if integrity == "checksum":
            raise ValueError(
                "integrity='checksum' is a level-synchronous ABFT lane; "
                "weighted rounds support integrity='audit'"
            )
        if not fuse_backward_payload:
            raise ValueError(
                "split backward payload is an unweighted sparse-engine "
                "benchmark mode"
            )
    if use_pallas:
        from repro.kernels.ops import resolve_interpret

        interpret = resolve_interpret(interpret)
    chunk = partition.chunk
    # Ring hops are mesh-wide collective-permutes: sub-cluster replicas
    # must stay in level-loop lockstep or the rendezvous deadlocks (the
    # extra levels a shallow replica runs are masked no-ops) — see
    # operators.DistributedOperator (sync_axes).
    sync_axes = (
        (replica_axis,) if replica_axis is not None and overlap != "none" else ()
    )

    def round_body(op, omega, sources, derived):
        out = traversal_round(
            op, sources[0], derived[0], omega, num_levels=num_levels,
            integrity=integrity,
        )
        # levels is grid-reduced but *per replica* (reduce_max_grid), the
        # straggler scheduler's cost signal — sharded on the replica axis.
        # With integrity on, a fifth slot carries the per-replica
        # [checksum residual, claimed bc sum] pair.
        return tuple(x[None] for x in out)

    if weighted:
        from repro.kernels.blocked_spmm import tiles_to_dense

        delta_f = float(delta)

        def weighted_dense_op(block):
            return DistributedWeightedDenseOperator(
                block,
                delta=delta_f,
                chunk=chunk,
                R=R,
                C=C,
                row_axis=row_axis,
                col_axis=col_axis,
                sync_axes=sync_axes,
            )

        if engine_kind == "sparse":

            def body(src_local, dst_local, w_local, omega, sources, derived):
                op = DistributedWeightedOperator(
                    src_local[0, 0],
                    dst_local[0, 0],
                    w_local[0, 0],
                    delta=delta_f,
                    chunk=chunk,
                    R=R,
                    C=C,
                    row_axis=row_axis,
                    col_axis=col_axis,
                    sync_axes=sync_axes,
                )
                return round_body(op, omega, sources, derived)

            graph_specs = (
                P(row_axis, col_axis, None),
                P(row_axis, col_axis, None),
                P(row_axis, col_axis, None),
            )
        elif engine_kind == "pallas_sparse":
            # weighted BCSR: ship the (weighted) tile layout, densify the
            # local cell in-body — same operands/specs as unweighted, but
            # the compute runs the dense weight-block bucket operator
            def body(tiles, trows, tcols, omega, sources, derived):
                block = tiles_to_dense(
                    tiles[0, 0], trows[0, 0], tcols[0, 0], C * chunk, R * chunk
                )
                return round_body(weighted_dense_op(block), omega, sources, derived)

            graph_specs = (
                P(row_axis, col_axis, None, None, None),
                P(row_axis, col_axis, None),
                P(row_axis, col_axis, None),
            )
        elif engine_kind == "pallas_hybrid":

            def body(blocks, tiles, trows, tcols, dcell, omega, sources, derived):
                from_tiles = tiles_to_dense(
                    tiles[0, 0], trows[0, 0], tcols[0, 0], C * chunk, R * chunk
                )
                block = jnp.where(dcell[0, 0] != 0, blocks[0, 0], from_tiles)
                return round_body(weighted_dense_op(block), omega, sources, derived)

            graph_specs = (
                P(row_axis, col_axis, None, None),
                P(row_axis, col_axis, None, None, None),
                P(row_axis, col_axis, None),
                P(row_axis, col_axis, None),
                P(row_axis, col_axis),
            )
        else:  # pallas / pallas_bf16: one f32 weight-block operand

            def body(blocks, omega, sources, derived):
                return round_body(
                    weighted_dense_op(blocks[0, 0]), omega, sources, derived
                )

            graph_specs = (P(row_axis, col_axis, None, None),)
    elif engine_kind == "pallas_sparse":
        # (tiles, tile_rows, tile_cols): [R, C, T, bm, bk]-shaped full
        # layout, or [R, C, R, Tr, bm, bk]-shaped ring slices — the two
        # layouts have the same arity, so one body serves both and the
        # static ``overlap`` decides which operator slots they fill.
        ring = overlap != "none"

        def body(tiles, trows, tcols, omega, sources, derived):
            local = (tiles[0, 0], trows[0, 0], tcols[0, 0])
            kw = (
                dict(ring_tiles=local[0], ring_tile_rows=local[1], ring_tile_cols=local[2])
                if ring
                else dict(tiles=local[0], tile_rows=local[1], tile_cols=local[2])
            )
            op = DistributedPallasSparseOperator(
                chunk=chunk,
                R=R,
                C=C,
                row_axis=row_axis,
                col_axis=col_axis,
                interpret=interpret,
                overlap=overlap,
                sync_axes=sync_axes,
                **kw,
            )
            return round_body(op, omega, sources, derived)

        nd = 6 if ring else 5  # tiles rank; index arrays are nd - 2
        graph_specs = (
            P(row_axis, col_axis, *([None] * (nd - 2))),
            P(row_axis, col_axis, *([None] * (nd - 4))),
            P(row_axis, col_axis, *([None] * (nd - 4))),
        )
    elif engine_kind == "pallas_hybrid":
        # (blocks, tiles, tile_rows, tile_cols, dense_cells): the dense
        # operand and the (possibly ring-sliced) tile layout travel
        # together; the i32 [R, C] choice mask tells each cell which one
        # it streams (lax.cond inside the operator's _partial_* hooks).
        ring = overlap != "none"

        def body(blocks, tiles, trows, tcols, dcell, omega, sources, derived):
            local = (tiles[0, 0], trows[0, 0], tcols[0, 0])
            kw = (
                dict(ring_tiles=local[0], ring_tile_rows=local[1], ring_tile_cols=local[2])
                if ring
                else dict(tiles=local[0], tile_rows=local[1], tile_cols=local[2])
            )
            op = DistributedPallasHybridOperator(
                blocks[0, 0],  # [C*chunk, R*chunk] local dense data (or zeros)
                dcell[0, 0] != 0,  # this cell's kernel choice
                chunk=chunk,
                R=R,
                C=C,
                row_axis=row_axis,
                col_axis=col_axis,
                interpret=interpret,
                overlap=overlap,
                sync_axes=sync_axes,
                **kw,
            )
            return round_body(op, omega, sources, derived)

        nd = 6 if ring else 5  # tiles rank; index arrays are nd - 2
        graph_specs = (
            P(row_axis, col_axis, None, None),
            P(row_axis, col_axis, *([None] * (nd - 2))),
            P(row_axis, col_axis, *([None] * (nd - 4))),
            P(row_axis, col_axis, *([None] * (nd - 4))),
            P(row_axis, col_axis),
        )
    elif use_pallas:

        def body(blocks, omega, sources, derived):
            op = DistributedPallasOperator(
                blocks[0, 0],  # [C*chunk, R*chunk] local dense block
                chunk=chunk,
                R=R,
                C=C,
                row_axis=row_axis,
                col_axis=col_axis,
                interpret=interpret,
                overlap=overlap,
                sync_axes=sync_axes,
            )
            return round_body(op, omega, sources, derived)

        graph_specs = (P(row_axis, col_axis, None, None),)
    elif overlap != "none":

        def body(ring_src, ring_dst, omega, sources, derived):
            op = DistributedOperator(
                None,
                None,
                chunk=chunk,
                R=R,
                C=C,
                row_axis=row_axis,
                col_axis=col_axis,
                overlap=overlap,
                ring_src_local=ring_src[0, 0],  # [R, max_ring_arcs] local view
                ring_dst_local=ring_dst[0, 0],
                sync_axes=sync_axes,
            )
            return round_body(op, omega, sources, derived)

        graph_specs = (
            P(row_axis, col_axis, None, None),
            P(row_axis, col_axis, None, None),
        )
    else:

        def body(src_local, dst_local, omega, sources, derived):
            op = DistributedOperator(
                src_local[0, 0],  # [max_arcs] local arc views
                dst_local[0, 0],
                chunk=chunk,
                R=R,
                C=C,
                row_axis=row_axis,
                col_axis=col_axis,
                split_backward=not fuse_backward_payload,
            )
            return round_body(op, omega, sources, derived)

        graph_specs = (
            P(row_axis, col_axis, None),
            P(row_axis, col_axis, None),
        )

    rep = (replica_axis,) if replica_axis is not None else (None,)
    in_specs = graph_specs + (
        P((col_axis, row_axis)),
        P(*rep, None),
        P(*rep, None, None),
    )
    out_specs = (
        P(*rep, (col_axis, row_axis)),
        P(*rep, None),
        P(*rep, None),
        P(*rep),
    )
    if integrity != "off":
        out_specs = out_specs + (P(*rep, None),)
    shmapped = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return jax.jit(shmapped)


def distributed_betweenness_centrality(
    graph: Graph,
    mesh: Mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    replica_axis: str | None = None,
    batch_size: int = 16,
    heuristics: str = "h0",
    num_levels: int | None = None,
    engine_kind: str = "sparse",
    overlap: str = "none",
    tile: tuple[int, int] | None = None,
    hybrid_threshold: float = 1.0,
    hbm_limit_bytes: float | None = None,
    ledger=None,
    checkpoint=None,
    straggler: str = "none",
    straggler_factor: float = 2.0,
    autotune: str = "off",
    autotune_cache=None,
    chaos=None,
    max_retries: int = DEFAULT_MAX_RETRIES,
    retry_backoff_s: float = DEFAULT_RETRY_BACKOFF_S,
    numeric_guard: bool | None = None,
    integrity: str = "off",
    dispatch_deadline_s=None,
    clock=None,
    sleeper=None,
    sampling: str = "off",
    sample_frac: float | None = None,
    sample_k: int | None = None,
    sample_seed: int = 0,
    stop_rule=None,
    full_result: bool = False,
    weighted: bool = False,
    delta: float | None = None,
):
    """Run the full distributed BC computation on ``mesh``.

    Rounds are dealt ``fr`` at a time (one per sub-cluster) by the shared
    :class:`repro.core.driver.BCDriver`; the replica merge sums the
    replica dim after the loop so a straggling/preempted replica's round
    can be re-issued (fault tolerance path, distributed/fault_tolerance.py).
    ``straggler`` selects the multi-ledger sub-cluster scheduling policy
    (:data:`repro.core.driver.STRAGGLER_POLICIES`): under ``"steal"`` or
    ``"redeal"`` the driver keeps one round ledger per replica, seeds its
    per-replica EWMA from the roofline prior
    (:func:`prior_round_seconds`) and moves uncommitted rounds between
    replica queues when one replica's per-round wall exceeds
    ``straggler_factor ×`` the fastest replica's; requires a
    ``replica_axis``.
    ``engine_kind`` selects the block-local compute
    (:data:`DIST_ENGINE_KINDS`: arc-list "sparse", fused dense-block
    "pallas"/"pallas_bf16", or blocked-sparse "pallas_sparse");
    ``overlap`` selects the collective schedule (barrier vs
    ring-pipelined — see :func:`make_distributed_round_fn`), with
    ``"auto"`` resolved from the roofline estimate
    (:func:`resolve_overlap`); ``tile`` overrides the blocked-sparse
    (bm, bk) tile shape.  With ``engine_kind="pallas_hybrid"`` the
    per-cell dense-vs-BCSR choice is resolved once from the roofline's
    bytes-streamed threshold (:func:`hybrid_cell_choice`, logged) and
    shared by the overlap resolve, the memory guard and the layout
    build; ``hybrid_threshold`` overrides the break-even point
    (0 forces all-dense, a large value all-sparse).
    ``hbm_limit_bytes`` arms the fail-fast
    memory guard (:func:`check_device_memory`): the per-device
    adjacency + state footprint is checked *before* compilation and an
    over-budget engine errors with a suggestion instead of OOMing
    mid-round.
    Each call records a run of host spans (:mod:`repro.core.spans`;
    ``BCResult.spans``): the root ``bc.entry``, set-up
    ``bc.setup.schedule`` (sampling plan, schedule; attrs
    ``derived_per_round`` and ``width``, the backward state's columns
    ``batch_size + derived_per_round``), ``.partition``
    (2-D partition, autotune when on, tile counts, overlap and memory
    resolution), ``.layout`` (the host operand layout) and ``.transfer``
    (the puts until the operands are resident), then the driver's.
    ``autotune`` (:data:`repro.autotune.AUTOTUNE_MODES`) swaps the
    roofline guesses behind the tile pick, the hybrid cell choice,
    ``overlap="auto"`` and the straggler prior for cached measurements
    (``"cache"``: consult only; ``"measure"``: micro-bench on a miss and
    record — measure-once), and switches the scheduler to
    eccentricity-packed rounds (``root_order="eccentricity"``) whose
    per-round depth prior seeds the replica deal.  ``autotune_cache`` is
    the persistent cache: a path, a :class:`repro.autotune.CostCache`,
    or None for in-memory.

    **Robustness.**  ``chaos`` (a ``--chaos`` spec string or
    :class:`repro.distributed.chaos.FaultPlan`) wraps the round fn in
    :class:`~repro.distributed.chaos.ChaosRoundFn` and the
    checkpoint/autotune-cache writers in the matching file-seam chaos
    wrappers, injecting the plan's faults deterministically; the
    unwrapped round fn doubles as the driver's ``fallback_round_fn``
    (known-good recompute path for persistently non-finite blocks).
    ``max_retries`` / ``retry_backoff_s`` / ``numeric_guard`` are the
    driver's self-healing knobs (core/driver.py); recovery telemetry
    lands in ``BCResult.recovery_stats`` (plus a ``"chaos"`` sub-dict
    with injection counters when a plan was active).

    ``integrity`` (:data:`repro.core.driver.INTEGRITY_MODES`) makes every
    round self-verifying: ``"audit"`` cross-checks each drained block
    against its in-graph claimed sum plus output-domain invariants
    (BC non-negativity, level bounds); ``"checksum"`` additionally runs
    the ABFT column-sum lane through every level SpMM.  A failed audit
    quarantines and re-dispatches the block (then the clean fallback,
    then :class:`~repro.distributed.fault_tolerance.IntegrityError`);
    under ``straggler="steal"`` duplicated tail rounds also get
    duplicate-vote SDC detection.  ``dispatch_deadline_s`` arms the
    dispatch watchdog — a float deadline in seconds, or ``"auto"`` for
    ``max(WATCHDOG_MIN_DEADLINE_S, WATCHDOG_SAFETY × prior round
    seconds)`` from the roofline/autotune prior; a dispatch exceeding it
    escalates hang → re-dispatch → replica loss (absorbed by the elastic
    re-mesh).  ``clock`` / ``sleeper`` are injectable time sources for
    the watchdog and the retry/stall sleeps (tests; default real time).
    Detection counters land in ``recovery_stats["integrity"]``.

    **Sampling** (``sampling`` — :data:`repro.serving.SAMPLING_MODES`):
    ``"fixed"`` runs a seeded root subset (``sample_frac`` /
    ``sample_k``) through the *same* scheduler — eccentricity packing,
    the replica deal, checkpoints and chaos all apply to the subset
    unchanged — and rescales the result by N/k; ``"adaptive"``
    additionally arms the driver's ``stop_rule`` seam (default
    :class:`repro.serving.AdaptiveStopRule`; override via ``stop_rule``,
    e.g. :class:`repro.serving.BlockBudgetStop` for serving refresh
    slices) so dispatch halts once the running accumulator's top-k
    ranks stabilize, rescaling by the roots actually committed.
    Requires ``heuristics="h0"`` (per-root additivity).  The expected
    sampled-run wall (rounds × the straggler prior's per-round seconds)
    is logged via :func:`repro.roofline.model.sampled_run_seconds`.

    ``full_result`` returns the :class:`~repro.core.driver.BCResult`
    instead of the legacy ``(bc, schedule)`` pair.

    **Weighted graphs.**  ``weighted=True`` runs the bucketed weighted
    traversal (delta-stepping-style distance buckets of width ``delta``,
    auto-derived from the weight distribution when None — see
    :func:`repro.core.operators.auto_delta`).  Requires edge weights on
    the graph, ``heuristics`` in
    :data:`repro.core.bc.WEIGHTED_HEURISTICS` (the level-based 2-degree
    rewrites assume unit edge lengths), no ``num_levels``, integrity
    ``"off"``/``"audit"`` (the checksum lane is level-synchronous) and
    ``autotune="off"`` (the micro-bench measures level-synchronous
    kernels).  ``overlap`` keeps its lockstep role but the collectives
    run the barrier schedule (ring-pipelined bucket relaxation is future
    work); the straggler prior prices bucket counts instead of levels
    (:func:`weighted_prior_levels`).
    """
    with span("bc.entry") as entry:
        from repro.autotune import as_cache, normalize_autotune, plan_autotune, sample_batch
        from repro.distributed.chaos import (
            ChaosCheckpoint,
            ChaosCostCache,
            ChaosFS,
            ChaosRoundFn,
            FaultPlan,
        )

        chaos_plan = FaultPlan.parse(chaos)
        chaos_fs = ChaosFS(chaos_plan) if chaos_plan else None
        if chaos_fs is not None:
            if isinstance(autotune_cache, (str, os.PathLike)):
                autotune_cache = ChaosCostCache(autotune_cache, chaos_fs)
            if checkpoint is not None:
                checkpoint = ChaosCheckpoint(checkpoint, chaos_fs)

        from repro.serving.sampling import (
            AdaptiveStopRule,
            eligible_roots,
            plan_sampling,
        )

        autotune = normalize_autotune(autotune)
        integrity = normalize_integrity(integrity)
        if weighted:
            from repro.core.bc import WEIGHTED_HEURISTICS

            if graph.w is None:
                raise ValueError(
                    "weighted=True needs edge weights: build the graph with "
                    "Graph.from_edges(..., weights=) or a weighted generator "
                    "(graphs.generators WEIGHT_MODES)"
                )
            if heuristics not in WEIGHTED_HEURISTICS:
                raise ValueError(
                    f"heuristics={heuristics!r} is level-based (2-degree "
                    f"derivation assumes unit edge lengths); weighted runs "
                    f"accept {WEIGHTED_HEURISTICS}"
                )
            if num_levels is not None:
                raise ValueError(
                    "num_levels is a static level bound for the level-"
                    "synchronous engine; the weighted bucket loop's trip "
                    "count is data-dependent"
                )
            if integrity == "checksum":
                raise ValueError(
                    "integrity='checksum' is a level-synchronous ABFT lane; "
                    "weighted runs support integrity='audit'"
                )
            if autotune != "off":
                raise ValueError(
                    "autotune measures the level-synchronous kernels; run "
                    "weighted with autotune='off'"
                )
            if delta is None:
                delta = auto_delta(graph)
            delta = float(delta)
            if not (delta > 0 and np.isfinite(delta)):
                raise ValueError(f"delta must be positive and finite, got {delta}")
        elif delta is not None:
            raise ValueError("delta is only meaningful with weighted=True")
        with span("bc.setup.schedule") as schedule_span:
            sample_plan = plan_sampling(
                eligible_roots(graph), sampling, sample_frac, sample_k, sample_seed
            )
            if sample_plan.mode != "off" and heuristics != "h0":
                raise ValueError(
                    "sampling requires heuristics='h0': the 1-/2-degree analytic "
                    "corrections are not per-root additive, so a sampled run "
                    "could not be rescaled into an unbiased estimator"
                )
            if stop_rule is not None and sample_plan.mode == "off":
                raise ValueError(
                    "a stop_rule truncates the schedule, which is only meaningful "
                    "as a rescaled estimate; pass sampling='fixed' or 'adaptive'"
                )
            if sample_plan.mode == "adaptive" and stop_rule is None:
                stop_rule = AdaptiveStopRule()
            schedule, prep, residual, omega_i = build_schedule(
                graph, batch_size=batch_size, heuristics=heuristics,
                root_order="eccentricity" if autotune != "off" else "id",
                roots=sample_plan.roots,
            )
            k = schedule.derived_per_round
            schedule_span.annotate(derived_per_round=k, width=schedule.batch_size + k)
        with span("bc.setup.partition") as partition_span:
            R, C, fr = _grid_axes(mesh, row_axis, col_axis, replica_axis)
            part = partition_2d(residual, R, C)

            plan = None
            if autotune != "off" and schedule.rounds:
                sources0, derived0 = sample_batch(schedule, fr)
                plan = plan_autotune(
                    part,
                    mesh,
                    engine_kind=engine_kind,
                    overlap=overlap,
                    batch_size=batch_size,
                    tile=tile,
                    mode=autotune,
                    cache=as_cache(autotune_cache),
                    graph=residual,
                    fr=fr,
                    row_axis=row_axis,
                    col_axis=col_axis,
                    replica_axis=replica_axis,
                    sources=sources0,
                    derived=derived0,
                    hybrid_threshold=hybrid_threshold,
                )
                if tile is None and plan.tile is not None:
                    tile = plan.tile
                logger.info("autotune[%s]: %s", autotune, plan.report())

            bm, bk = tile if tile is not None else (None, None)
            # ONE host arc→tile counting pass (cached on the partition) serves
            # the hybrid cell choice, the auto-overlap estimate, the memory
            # guard, and the layout build that follows
            tile_counts = (
                part.blocked_sparse_counts(bm, bk)
                if engine_kind in ("pallas_sparse", "pallas_hybrid")
                else None
            )
            dense_cells = None
            if engine_kind == "pallas_hybrid":
                dense_cells, _ = hybrid_cell_choice(
                    part, bm, bk, threshold=hybrid_threshold, tile_counts=tile_counts,
                    measured=plan.cell_costs if plan is not None else None,
                )
            if weighted:
                # weighted collectives run the barrier schedule; overlap only
                # keeps replicas in bucket-loop lockstep, so "auto" has nothing
                # to price — resolve it to the barrier policy
                if overlap == "auto":
                    logger.info("overlap='auto' -> 'none' (weighted rounds are barrier-schedule)")
                    overlap = "none"
                overlap = normalize_overlap(overlap)
            else:
                overlap = resolve_overlap(
                    overlap, part, engine_kind, batch_size,
                    bm=bm, bk=bk, tile_counts=tile_counts, dense_cells=dense_cells,
                    measured=plan.overlap_level_s if plan is not None else None,
                )
            check_device_memory(
                part, engine_kind, batch_size, hbm_limit_bytes,
                bm=bm, bk=bk, overlap="none" if weighted else overlap,
                tile_counts=tile_counts, dense_cells=dense_cells,
            )
            # what a level runs and ships per chip; weighted rounds run
            # barrier collectives once per bucket relaxation, not per level
            ring = overlap != "none" and not weighted
            partition_span.annotate(
                mesh=f"{R}x{C}", overlap=overlap, chunk=part.chunk,
                ring_steps=R if ring else 1,
            )
            if not weighted:
                lanes = schedule.batch_size + k + (integrity == "checksum")
                fwd_bytes, bwd_bytes = exchange_bytes(part, engine_kind, lanes)
                partition_span.annotate(
                    exchange_bytes_fwd=fwd_bytes, exchange_bytes_bwd=bwd_bytes
                )

        round_fn = make_distributed_round_fn(
            part,
            mesh,
            row_axis=row_axis,
            col_axis=col_axis,
            replica_axis=replica_axis,
            num_levels=num_levels,
            engine_kind=engine_kind,
            overlap=overlap,
            integrity=integrity,
            weighted=weighted,
            delta=delta,
        )

        from jax.sharding import NamedSharding

        with span("bc.setup.layout") as layout_span:
            omega_pad = np.zeros(part.n_pad, np.float32)
            omega_pad[: graph.n] = omega_i
            host_args = graph_host_arrays(
                part, engine_kind, overlap, tile=tile, dense_cells=dense_cells,
                weights=residual.w if weighted else None,
            )
            if engine_kind == "pallas_sparse":
                layout_span.annotate(
                    tile_slots=tile_counts["stored_tiles_ring" if ring else "stored_tiles_full"],
                    tiles_stored=tile_counts["stored_tiles_full"],
                )
        nbytes = omega_pad.nbytes + sum(a.nbytes for a in host_args)
        with span("bc.setup.transfer", bytes=nbytes):
            # reorder omega into chunk-owner layout: flat position = chunk-id*chunk + off
            # chunk ids are contiguous in vertex order, so identity layout works.
            omega_dev = jax.device_put(
                omega_pad, NamedSharding(mesh, P((col_axis, row_axis)))
            )
            graph_args = put_graph_arrays(
                host_args, mesh=mesh, row_axis=row_axis, col_axis=col_axis
            )
            # resident before block 1 is dispatched, so the span ends with the copy
            jax.block_until_ready((omega_dev, graph_args))
        del host_args

        def block_fn(sources, derived):
            return round_fn(*graph_args, omega_dev, sources, derived)

        from repro.core.driver import normalize_straggler

        straggler = normalize_straggler(straggler)
        prior_round_s = None
        if (
            straggler != "none"
            or dispatch_deadline_s == "auto"
            or sample_plan.mode != "off"
        ):
            if straggler != "none" and replica_axis is None:
                raise ValueError(
                    "straggler scheduling re-deals rounds between sub-cluster "
                    "replicas; pass replica_axis (a mesh with fr > 1)"
                )
            prior_round_s = prior_round_seconds(
                part, engine_kind, batch_size, "none" if weighted else overlap,
                bm=bm, bk=bk, tile_counts=tile_counts, dense_cells=dense_cells,
                measured_level_s=(
                    plan.level_s_for(overlap) if plan is not None else None
                ),
                prior_levels=(
                    weighted_prior_levels(residual.w, delta) if weighted else None
                ),
            )
        if sample_plan.mode != "off":
            from repro.roofline.model import sampled_run_seconds

            logger.info(
                "sampling[%s]: %d of %d eligible roots in %d rounds "
                "(seed %d); expected wall ≈ %.3gs at the %.3gs/round prior",
                sample_plan.mode, sample_plan.k, sample_plan.num_eligible,
                len(schedule.rounds), sample_plan.seed,
                sampled_run_seconds(len(schedule.rounds), fr, prior_round_s),
                prior_round_s,
            )
        if dispatch_deadline_s == "auto":
            # generous on purpose: the prior models steady-state rounds, but
            # the first dispatch pays jit compilation on top
            dispatch_deadline_s = max(
                WATCHDOG_MIN_DEADLINE_S, WATCHDOG_SAFETY * float(prior_round_s)
            )
            logger.info("dispatch watchdog: auto deadline %.1fs", dispatch_deadline_s)

        dispatch_fn = block_fn
        fallback_fn = None
        if chaos_plan:
            dispatch_fn = ChaosRoundFn(block_fn, chaos_plan, sleeper=sleeper)
            fallback_fn = block_fn  # the unwrapped, known-good path

        level_bound = None
        if weighted:
            # the audit's "levels" are bucket indices: ≤ ⌈(n-1)·w_max/Δ⌉
            w_max = float(residual.w.max()) if residual.w.size else 1.0
            level_bound = int(np.ceil(graph.n * w_max / delta)) + 2

        driver = BCDriver(
            dispatch_fn,
            schedule,
            n=graph.n,
            prep=prep,
            level_bound=level_bound,
            ledger=ledger,
            checkpoint=checkpoint,
            rounds_per_dispatch=fr,
            straggler=straggler,
            straggler_factor=straggler_factor,
            prior_round_s=prior_round_s,
            round_costs=schedule.round_depths,
            max_retries=max_retries,
            retry_backoff_s=retry_backoff_s,
            numeric_guard=numeric_guard,
            fallback_round_fn=fallback_fn,
            integrity=integrity,
            dispatch_deadline_s=dispatch_deadline_s,
            clock=clock,
            sleeper=sleeper,
            stop_rule=stop_rule,
            # the planner's taxonomy for elastic re-mesh on replica loss:
            # replica lanes are 'pod' groups, the grid is data × model
            mesh_shape=(fr, R, C),
            mesh_axes=("pod", "data", "model"),
        )
        result = driver.run()
        from repro.core.bc import apply_sampling_rescale

        result = apply_sampling_rescale(result, sample_plan)
        if chaos_plan:
            result.recovery_stats["chaos"] = {
                "plan": repr(chaos_plan),
                "dispatch_calls": dispatch_fn.calls,
                "checkpoint_saves": chaos_fs.checkpoint_saves,
                "cache_puts": chaos_fs.cache_puts,
                "files_corrupted": list(chaos_fs.files_corrupted),
            }
    attach_run(entry, result)
    if full_result:
        return result
    return result.bc, schedule
