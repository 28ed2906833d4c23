"""TPU v5e roofline model: three terms per (arch × mesh) cell.

    compute    = HLO_FLOPs_per_device / peak_FLOPs
    memory     = HLO_bytes_per_device / HBM_bandwidth
    collective = link_bytes_per_device / ICI_link_bandwidth

Link bytes apply the standard ring-algorithm weights to the collective
operand bytes the HLO parser recorded (g = participant group size):

    all-gather          (g-1)   · operand        (tiled operand = shard)
    reduce-scatter      (g-1)/g · operand
    all-reduce        2·(g-1)/g · operand
    all-to-all          (g-1)/g · operand
    collective-permute            operand

Ring schedules additionally pay a per-step launch latency (α in the
α-β model): every ring hop is a ppermute with its own synchronization,
so a collective decomposed into k steps costs k·α + bytes/β.
``ring_steps`` counts the hops each collective class implies,
``ring_latency_s`` prices them, and ``overlap_step_time`` estimates the
pipelined level time max(T_comm, T_comp) + min(T_comm, T_comp)/k that
the ring-pipelined expand/fold schedule converges to (the barrier
schedule pays T_comm + T_comp).
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "V5E",
    "HARDWARE",
    "HardwareSpec",
    "device_hardware",
    "RooflineTerms",
    "roofline_terms",
    "link_bytes",
    "ring_steps",
    "ring_latency_s",
    "overlap_step_time",
    "adjacency_stream_bytes",
    "sparse_tile_bytes",
    "cell_kernel_choice",
    "device_hbm_footprint",
    "auto_overlap_policy",
    "exchange_operands",
    "sampled_run_seconds",
    "TILE_OVERHEAD_BYTES",
]


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_bf16_flops: float  # per chip
    hbm_bandwidth: float  # bytes/s per chip
    ici_link_bandwidth: float  # bytes/s per link
    ici_step_latency_s: float = 1e-6  # per ring-hop launch/sync latency (α)


V5E = HardwareSpec(
    name="tpu-v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
)

#: Peak rates by ``jax.Device.device_kind`` — the one table every roofline
#: price reads.  v5e: 197 TFLOP/s bf16 and 819 GB/s HBM per chip (Google
#: Cloud documentation, "TPU v5e").
HARDWARE: dict[str, HardwareSpec] = {"TPU v5 lite": V5E}


def device_hardware(device=None) -> HardwareSpec:
    """The :class:`HardwareSpec` of ``device`` (default: the first device).

    A TPU whose ``device_kind`` is not in :data:`HARDWARE` raises: a
    roofline priced with another chip's peaks is wrong, not approximate.
    The CPU backend has no peaks of its own worth pricing with; it plans
    as a v5e chip, so CPU rehearsals make the same schedule choices the
    chip run would.  Any other platform raises.
    """
    import jax

    device = jax.devices()[0] if device is None else device
    if device.platform == "cpu":
        return V5E
    spec = HARDWARE.get(device.device_kind)
    if spec is None:
        raise ValueError(
            f"no peak-rate entry for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add it to "
            f"repro.roofline.model.HARDWARE with its published source"
        )
    return spec


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    bytes: float
    link_bytes: float
    bottleneck: str
    model_flops_total: float
    useful_fraction: float  # MODEL_FLOPS / (HLO flops × devices)
    ring_steps: int = 0  # total ring hops implied by the collectives
    ring_latency_s: float = 0.0  # α term: ring_steps · per-hop latency

    @property
    def step_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Dominant-term share of the no-overlap ideal (1.0 = the step is
        exactly its dominant roofline term; <1 impossible here — reported
        as dominant/sum to show overlap headroom)."""
        total = self.compute_s + self.memory_s + self.collective_s
        return self.step_time_s / total if total > 0 else 0.0


def link_bytes(coll_records: list[dict]) -> float:
    total = 0.0
    for rec in coll_records:
        g = max(rec.get("group_size", 1), 1)
        b = rec["operand_bytes"]
        cls = rec["class"]
        if cls == "all-gather":
            total += (g - 1) * b
        elif cls == "reduce-scatter":
            total += (g - 1) / g * b
        elif cls == "all-reduce":
            total += 2 * (g - 1) / g * b
        elif cls == "all-to-all":
            total += (g - 1) / g * b
        else:  # collective-permute, broadcast
            total += b
    return total


def ring_steps(coll_records: list[dict]) -> int:
    """Total ring hops the recorded collectives imply (α-model step count).

    A monolithic collective over a group of g devices runs a g-1-hop
    ring internally (2·(g-1) for all-reduce = reduce-scatter +
    all-gather); an explicit collective-permute IS one hop.  Records
    carry a ``count`` when they aggregate several instruction sites
    (roofline/hlo.py multiplies it by loop trip counts).  Comparing
    this count between the barrier and pipelined lowerings of the same
    level shows the latency-term price of the overlap schedule.
    """
    total = 0
    for rec in coll_records:
        g = max(rec.get("group_size", 1), 1)
        sites = max(rec.get("count", 1), 1)
        cls = rec["class"]
        if cls == "all-reduce":
            total += sites * 2 * (g - 1)
        elif cls in ("all-gather", "reduce-scatter", "all-to-all"):
            total += sites * (g - 1)
        else:  # collective-permute, broadcast: a single hop each
            total += sites
    return total


def ring_latency_s(coll_records: list[dict], hw: HardwareSpec = V5E) -> float:
    """α term: per-hop launch latency summed over every implied ring hop."""
    return ring_steps(coll_records) * hw.ici_step_latency_s


def overlap_step_time(compute_s: float, collective_s: float, k: int) -> float:
    """Pipelined level-time estimate for a k-step ring schedule.

    The barrier schedule pays compute + collective in sequence.  A ring
    schedule splits both into k per-chunk slices and overlaps slice i's
    transfer with slice i-1's compute, so only the first (or last) slice
    of the minor term is exposed:

        max(T_comp, T_comm) + min(T_comp, T_comm) / k
    """
    if k <= 1:
        return compute_s + collective_s
    lo, hi = sorted((compute_s, collective_s))
    return hi + lo / k


def sampled_run_seconds(num_rounds: int, fr: int, round_s: float) -> float:
    """Wall estimate of a (sampled) run: dispatch blocks × per-round wall.

    The sampled-cost bridge between the per-round prior
    (:func:`repro.core.distributed.prior_round_seconds`) and the serving
    layer: a k-root sample schedules ``ceil(k / batch)`` rounds dealt
    ``fr`` per dispatch block, so its cost is the block count times the
    same per-round prior the straggler EWMA is seeded from — which is
    what ``launch/serve_bc.py`` uses to budget refresh slices and what
    the entrypoints log as the expected sampled-run wall.
    """
    if num_rounds <= 0:
        return 0.0
    blocks = -(-int(num_rounds) // max(1, int(fr)))  # ceil division
    return blocks * float(round_s)


# ---------------------------------------------------------------------------
# Per-engine adjacency model for the 2-D distributed path.  The roofline
# historically priced the A-stream dense — O(n_pad²/p) per device per
# level — which is wrong by orders of magnitude for the blocked-sparse
# engine on RMAT-scale graphs; ``adjacency_stream_bytes`` is the
# per-engine quantity (dense block, arc list, or nnz-tile list) used by
# both the memory guard and the sparse benchmark record.
# ---------------------------------------------------------------------------

#: payload per exchanged direction, as ([chunk, s] state blocks, [chunk]
#: vectors): the arc-list engine ships a single pre-masked block; the
#: fused Pallas engines (dense-block, blocked-sparse, and the per-cell
#: hybrid of the two) ship (σ, d) forward and (σ, d, δ) + ω backward
#: (paper §3.2 exchange set).
_EXCHANGE_OPERANDS = {
    "sparse": ((1, 0), (1, 0)),
    "pallas": ((2, 0), (3, 1)),
    "pallas_bf16": ((2, 0), (3, 1)),
    "pallas_sparse": ((2, 0), (3, 1)),
    "pallas_hybrid": ((2, 0), (3, 1)),
}

#: per-stored-tile scalar-prefetch/grid-step overhead allowance of the
#: blocked-sparse kernels, in equivalent HBM bytes: the 8 B row/col
#: index maps each tile DMAs plus a flat allowance for the per-grid-step
#: control cost (index-map evaluation, accumulator init/flush bookkeeping)
#: that the dense kernels amortize over whole 128-blocks.  Used only by
#: the per-cell dense-vs-BCSR choice (:func:`cell_kernel_choice`) — the
#: memory guard prices the stored bytes (:func:`sparse_tile_bytes`)
#: without the allowance.
TILE_OVERHEAD_BYTES = 32.0


def sparse_tile_bytes(bm: int, bk: int, elem: int = 4) -> int:
    """Stored bytes of one blocked-sparse tile: data + 8 B index maps."""
    return bm * bk * elem + 8


def cell_kernel_choice(
    stored_tiles_cell: np.ndarray,
    *,
    R: int,
    C: int,
    chunk: int,
    bm: int,
    bk: int,
    threshold: float = 1.0,
    elem: int = 4,
    measured: tuple[float, float] | None = None,
) -> np.ndarray:
    """Per-device-cell dense-vs-BCSR kernel pick (bool [R, C], True = dense).

    On skewed (RMAT-like) graphs the 2-D decomposition hands each device
    a block whose density varies wildly across the mesh — the
    community-structured cells are near-dense while the off-diagonal
    cells are hyper-sparse — so a single global engine choice always
    wastes either HBM bandwidth (dense streaming of near-empty blocks)
    or tile-index overhead (BCSR streaming of near-full blocks).  This
    prices what each cell actually streams per traversal level:

        dense:  (C·chunk)·(R·chunk)·elem          — the cell's n_pad²/p share
        BCSR:   stored · (bm·bk·elem + 8 + TILE_OVERHEAD_BYTES)

    and picks dense where ``bcsr >= threshold · dense``.
    ``stored_tiles_cell`` is the per-cell *stored* tile count (true
    nonzero tiles + row-complete fillers —
    ``TwoDPartition.blocked_sparse_counts()["stored_full_cell"]``), the
    count the kernel's grid actually iterates.  ``threshold`` is the
    ``--hybrid-threshold`` knob: 0 forces every cell dense, a huge value
    forces every cell sparse, 1.0 is the break-even default.

    ``measured`` replaces the bytes model with a measured calibration
    pair ``(dense_level_s, sparse_level_s)`` from the autotune cache
    (:mod:`repro.autotune`): the pure-dense per-level wall prices every
    cell's dense cost, the pure-BCSR wall divided by the total stored
    tiles prices one tile, and a cell goes dense where
    ``stored · per_tile_s >= threshold · dense_level_s`` — same
    break-even rule, measured seconds instead of modelled bytes.
    """
    stored = np.asarray(stored_tiles_cell, np.float64)
    if stored.shape != (R, C):
        raise ValueError(f"stored_tiles_cell shape {stored.shape} != {(R, C)}")
    if measured is not None:
        dense_level_s, sparse_level_s = (float(x) for x in measured)
        per_tile_s = sparse_level_s / max(float(stored.max()), 1.0)
        return stored * per_tile_s >= threshold * dense_level_s
    dense_bytes = float(C * chunk) * (R * chunk) * elem
    bcsr_bytes = stored * (sparse_tile_bytes(bm, bk, elem) + TILE_OVERHEAD_BYTES)
    return bcsr_bytes >= threshold * dense_bytes


def exchange_operands(engine_kind: str) -> tuple[tuple[int, int], tuple[int, int]]:
    """(forward, backward) per-level exchange set of an engine, each as
    ([chunk, s] state blocks, [chunk] vectors).

    The single source of the §3.2 exchange-set table above: the arc-list
    engine gathers one pre-masked block per direction; the fused-kernel
    engines exchange (σ, d) forward and (σ, d, δ) + ω backward.  Consumed
    by the state-footprint model here and the per-level exchange bytes
    in :func:`repro.core.distributed.exchange_bytes`.
    """
    return _EXCHANGE_OPERANDS[engine_kind]


def adjacency_stream_bytes(
    engine_kind: str,
    *,
    R: int,
    C: int,
    chunk: int,
    nnz_tiles: int | None = None,
    bm: int | None = None,
    bk: int | None = None,
    max_arcs: int | None = None,
) -> float:
    """Per-device A-stream bytes of one traversal level.

    dense Pallas engines   (C·chunk)·(R·chunk)·elem   — the full block
    blocked-sparse engine  nnz_tiles·bm·bk·elem + index maps
    arc-list engine        2·max_arcs·4               — (src, dst) i32
    hybrid engine          dense block + the sparse tile list — the
                           *resident* union the mixed layout ships with
                           shard_map-uniform shapes (the guard's
                           quantity); what one cell actually streams per
                           level is its chosen representation
                           (:func:`cell_kernel_choice`), priced per cell
                           in ``repro.core.distributed.level_time_estimates``.

    ``nnz_tiles`` is whatever tile count the caller wants priced: the
    true nonzero count for a best-case stream model, or the layout's
    *stored* count (fillers + padding + ring slots,
    ``TwoDPartition.blocked_sparse_counts``) for the bytes actually
    allocated/streamed — the memory guard passes the latter (for the
    hybrid engine: the sparse-chosen cells' masked counts, so the guard
    prices the actually-shipped mixed layout).
    """
    if engine_kind in ("pallas", "pallas_bf16"):
        elem = 2 if engine_kind == "pallas_bf16" else 4
        return float(C * chunk) * (R * chunk) * elem
    if engine_kind in ("pallas_sparse", "pallas_hybrid"):
        if None in (nnz_tiles, bm, bk):
            raise ValueError(f"{engine_kind} needs nnz_tiles, bm, bk")
        tiles = float(nnz_tiles) * sparse_tile_bytes(bm, bk)
        if engine_kind == "pallas_sparse":
            return tiles
        # dense-block operand + sparse tile list + the i32 cell choice
        return float(C * chunk) * (R * chunk) * 4 + tiles + 4
    if engine_kind == "sparse":
        if max_arcs is None:
            raise ValueError("sparse needs max_arcs")
        return float(2 * max_arcs) * 4
    raise ValueError(f"unknown distributed engine {engine_kind!r}")


def device_hbm_footprint(
    engine_kind: str,
    *,
    R: int,
    C: int,
    chunk: int,
    batch_size: int,
    nnz_tiles: int | None = None,
    bm: int | None = None,
    bk: int | None = None,
    max_arcs: int | None = None,
) -> dict:
    """Per-device HBM footprint (bytes) of one distributed BC round.

    ``adjacency``: the resident graph operand (engine-dependent — the
    quantity that decides dense-vs-sparse feasibility).  ``state``: owned
    (σ, δ f32 + d i32 + ω, bc f32) columns, the worst-case gathered
    operand slice ([R·chunk, s] × exchanged tensors), and the [C·chunk, s]
    fold partial.  An estimate for fail-fast guarding — XLA temp buffers
    add a constant factor, but the dense-block OOM this guard exists to
    catch is orders of magnitude, not percent.
    """
    s = batch_size
    adjacency = adjacency_stream_bytes(
        engine_kind,
        R=R,
        C=C,
        chunk=chunk,
        nnz_tiles=nnz_tiles,
        bm=bm,
        bk=bk,
        max_arcs=max_arcs,
    )
    _, (blocks, vectors) = _EXCHANGE_OPERANDS[engine_kind]
    state = (
        3 * chunk * s * 4  # owned σ, d, δ
        + 2 * chunk * 4  # ω, bc accumulator
        + (blocks * s + vectors) * R * chunk * 4  # gathered operand slice (worst: bwd)
        + C * chunk * s * 4  # pre-fold partial
    )
    return {
        "engine_kind": engine_kind,
        "adjacency_bytes": float(adjacency),
        "state_bytes": float(state),
        "total_bytes": float(adjacency + state),
    }


def auto_overlap_policy(
    compute_s: float,
    expand_s: float,
    fold_s: float,
    R: int,
    C: int,
    hw: HardwareSpec = V5E,
    measured: dict | None = None,
) -> tuple[str, dict]:
    """Pick the ring policy from the ``overlap_step_time`` estimate.

    Prices one traversal level under the three schedules — barrier
    (compute + both collectives in sequence), ``expand`` (gather
    pipelined into R hops, fold still a barrier), ``expand+fold`` (both
    collectives ring-decomposed) — each ring hop paying the α launch
    latency on top of the pipelined β term.  Returns the winning policy
    and the per-policy estimates (logged by the caller so the choice is
    auditable and overridable).

    ``measured`` maps policy -> measured per-level seconds from the
    autotune cache (:mod:`repro.autotune`).  When any policy has a
    measurement, the pick compares *measured policies only* (measured
    walls and model seconds are not on the same scale) and the returned
    estimates dict carries the measured values in place of the modelled
    ones, so the caller's audit log shows what the choice actually
    compared.
    """
    alpha = hw.ici_step_latency_s
    estimates = {
        "none": compute_s + expand_s + fold_s,
        "expand": overlap_step_time(compute_s, expand_s, R)
        + fold_s
        + (R - 1) * alpha,
        "expand+fold": overlap_step_time(compute_s, expand_s + fold_s, R)
        + (R - 1 + C - 1) * alpha,
    }
    if measured:
        known = {
            p: float(s) for p, s in measured.items()
            if p in estimates and s is not None
        }
        if known:
            estimates.update(known)
            return min(known, key=known.get), estimates
    return min(estimates, key=estimates.get), estimates


def roofline_terms(
    hlo_terms: dict,
    n_devices: int,
    model_flops_total: float = 0.0,
    hw: HardwareSpec = V5E,
) -> RooflineTerms:
    """hlo_terms: output of analyze_hlo_module (per-device quantities)."""
    flops = hlo_terms["flops"]
    mem_bytes = hlo_terms["bytes"]
    colls = hlo_terms.get("collectives", [])
    lb = link_bytes(colls)
    steps = ring_steps(colls)
    compute_s = flops / hw.peak_bf16_flops
    memory_s = mem_bytes / hw.hbm_bandwidth
    collective_s = lb / hw.ici_link_bandwidth
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = (
        model_flops_total / (flops * n_devices) if flops > 0 and model_flops_total else 0.0
    )
    return RooflineTerms(
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        flops=flops,
        bytes=mem_bytes,
        link_bytes=lb,
        bottleneck=bottleneck,
        model_flops_total=model_flops_total,
        useful_fraction=useful,
        ring_steps=steps,
        ring_latency_s=steps * hw.ici_step_latency_s,
    )
