"""Micro-bench harness + staged planner for the measured-cost cache.

``plan_autotune`` is the one entry: given a partition/mesh and the
requested run config, it resolves measured per-level costs for every
choice the run is about to make, consulting the :class:`CostCache`
first and (in ``"measure"`` mode) micro-benching on a miss.  Three
bounded stages keep a cold run to a handful of timings instead of a
cross product:

  1. **tile** — candidate BCSR tile shapes
     (:meth:`TwoDPartition.tile_candidates`), each timed as a pure
     ``pallas_sparse`` round at ``overlap="none"`` (the tile shape
     prices the BCSR side regardless of the surrounding engine).
  2. **hybrid calibration** — for ``pallas_hybrid``, one pure dense
     (``pallas``) and one pure BCSR (``pallas_sparse``) timing: the
     (dense_level_s, sparse_level_s) pair
     :func:`repro.roofline.model.cell_kernel_choice` consumes.
  3. **overlap** — the requested policy (or all of
     ``OVERLAP_POLICIES`` under ``overlap="auto"``) timed on the final
     engine/tile; these seed :func:`auto_overlap_policy` and the
     straggler prior (:func:`distributed.prior_round_seconds`).

Each timing runs the *real* distributed round function for a few
representative levels (``MEASURE_LEVELS``), 1 warm-up + ``MEASURE_ITERS``
timed calls, and records ``min(walls) / (2 · levels)`` — forward +
backward both sweep the level loop, hence the 2.  The wall clock and
the whole bench callable are injectable, so on CPU fake devices unit
tests drive the path with deterministic fake clocks (the
``tests/test_straggler.py`` trick).

When measured and roofline costs would otherwise mix (some candidates
cached, others not, in ``"cache"`` mode), comparisons restrict to the
measured candidates only — CPU-interpreter walls and model seconds are
not on the same scale, so a measured-vs-modelled comparison would be
meaningless.  ``"measure"`` mode never mixes: every candidate it
compares, it measures.
"""
from __future__ import annotations

import dataclasses
import logging
import time

import numpy as np

from repro.autotune.cache import (
    CostCache,
    CostRecord,
    config_key,
    graph_key_for,
    normalize_autotune,
)
from repro.core.operators import OVERLAP_POLICIES, normalize_overlap

logger = logging.getLogger(__name__)

#: static level bound of a micro-bench round: deep enough to amortize
#: per-round dispatch overhead, shallow enough that a cold autotune adds
#: only a few round-equivalents of work
MEASURE_LEVELS = 4
MEASURE_ITERS = 2
MEASURE_WARMUP = 1

#: engines whose graph operands are BCSR-tiled (tile stage applies)
TILED_ENGINES = ("pallas_sparse", "pallas_hybrid")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One measurable config (the cache's config-key tuple)."""

    engine_kind: str
    overlap: str
    batch_size: int
    tile: tuple[int, int] | None = None

    def key(self) -> str:
        return config_key(self.engine_kind, self.overlap, self.batch_size, self.tile)


def measure_walls(run, *, clock=time.perf_counter, warmup: int = MEASURE_WARMUP,
                  iters: int = MEASURE_ITERS) -> list[float]:
    """Time ``run()``: ``warmup`` untimed calls (compile), then ``iters``
    timed calls.  Returns the raw walls; callers take the min (the
    least-interfered sample) as the cost."""
    for _ in range(warmup):
        run()
    walls = []
    for _ in range(iters):
        t0 = clock()
        run()
        walls.append(clock() - t0)
    return walls


def default_bench(
    partition,
    mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    replica_axis: str | None = None,
    sources: np.ndarray,
    derived: np.ndarray,
    hybrid_threshold: float = 1.0,
    clock=time.perf_counter,
):
    """Build the production bench callable: Candidate -> CostRecord.

    Lowers the real distributed round function at ``MEASURE_LEVELS``
    static levels with the candidate's engine/overlap/tile operands and
    times it on the mesh.  Imports the distributed module lazily — the
    autotune package is imported *by* it.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import (
        distributed_graph_arrays,
        hybrid_cell_choice,
        make_distributed_round_fn,
    )

    omega = jnp.zeros(partition.R * partition.C * partition.chunk, jnp.float32)
    sources = jnp.asarray(sources)
    derived = jnp.asarray(derived)

    def bench(cand: Candidate) -> CostRecord:
        bm, bk = cand.tile if cand.tile is not None else (None, None)
        dense_cells = None
        if cand.engine_kind == "pallas_hybrid":
            dense_cells, _ = hybrid_cell_choice(
                partition, bm, bk, threshold=hybrid_threshold
            )
        round_fn = make_distributed_round_fn(
            partition,
            mesh,
            row_axis=row_axis,
            col_axis=col_axis,
            replica_axis=replica_axis,
            num_levels=MEASURE_LEVELS,
            engine_kind=cand.engine_kind,
            overlap=cand.overlap,
        )
        graph_args = distributed_graph_arrays(
            partition,
            cand.engine_kind,
            cand.overlap,
            tile=cand.tile,
            dense_cells=dense_cells,
            mesh=mesh,
            row_axis=row_axis,
            col_axis=col_axis,
        )

        def run():
            jax.block_until_ready(round_fn(*graph_args, omega, sources, derived))

        walls = measure_walls(run, clock=clock)
        return CostRecord(
            level_s=min(walls) / (2.0 * MEASURE_LEVELS),
            levels=MEASURE_LEVELS,
            walls=tuple(walls),
        )

    return bench


def sample_batch(schedule, fr: int) -> tuple[np.ndarray, np.ndarray]:
    """A representative (sources, derived) block for the micro-bench:
    the schedule's first round, replicated across the ``fr`` lanes."""
    r0 = schedule.rounds[0]
    sources = np.tile(np.asarray(r0.sources, np.int32), (fr, 1))
    derived = np.tile(np.asarray(r0.derived, np.int32), (fr, 1, 1))
    return sources, derived


@dataclasses.dataclass
class TunePlan:
    """Resolved measured costs for one run (what the seams consume)."""

    mode: str
    graph_key: str
    engine_kind: str
    batch_size: int
    #: resolved BCSR tile (None for untiled engines / no candidates)
    tile: tuple[int, int] | None = None
    #: "explicit" | "measured" | "roofline" | "default"
    tile_source: str = "default"
    #: measured (dense_level_s, sparse_level_s) hybrid calibration pair,
    #: None when either half is unmeasured (seam falls back to roofline)
    cell_costs: tuple[float, float] | None = None
    #: measured per-level seconds per overlap policy (only policies with
    #: a cache hit or fresh measurement appear)
    overlap_level_s: dict = dataclasses.field(default_factory=dict)
    hits: int = 0
    misses: int = 0
    measured: int = 0

    def level_s_for(self, policy: str) -> float | None:
        """Measured per-level cost of the (resolved) overlap policy —
        the straggler EWMA prior's seed."""
        return self.overlap_level_s.get(normalize_overlap(policy))

    def report(self) -> dict:
        """The dryrun/CLI ``[tune]`` record."""
        return {
            "mode": self.mode,
            "graph_key": self.graph_key,
            "tile": list(self.tile) if self.tile else None,
            "tile_source": self.tile_source,
            "overlap_level_s": {
                k: round(v, 9) for k, v in sorted(self.overlap_level_s.items())
            },
            "cell_costs_measured": self.cell_costs is not None,
            "hits": self.hits,
            "misses": self.misses,
            "measured": self.measured,
        }


def plan_autotune(
    partition,
    mesh=None,
    *,
    engine_kind: str,
    overlap: str,
    batch_size: int,
    tile: tuple[int, int] | None = None,
    mode: str = "measure",
    cache: CostCache | None = None,
    graph=None,
    nnz_tiles: int = 0,
    fr: int = 1,
    row_axis: str = "data",
    col_axis: str = "model",
    replica_axis: str | None = None,
    sources: np.ndarray | None = None,
    derived: np.ndarray | None = None,
    hybrid_threshold: float = 1.0,
    bench=None,
    clock=time.perf_counter,
) -> TunePlan:
    """Resolve measured costs for a run (see module docstring).

    ``bench`` overrides the measurement callable (Candidate ->
    CostRecord) — fake-clock unit tests inject a deterministic one; the
    default lowers and times real round functions on ``mesh``.
    """
    mode = normalize_autotune(mode)
    cache = cache if cache is not None else CostCache(None)
    gkey = graph_key_for(partition, graph, fr=fr, nnz_tiles=nnz_tiles)
    plan = TunePlan(
        mode=mode, graph_key=gkey, engine_kind=engine_kind, batch_size=batch_size
    )
    if mode == "off":
        return plan

    _bench = bench

    def get_bench():
        nonlocal _bench
        if _bench is None:
            if mesh is None:
                raise ValueError(
                    "autotune='measure' needs a mesh (or an injected bench) "
                    "to time candidate configs"
                )
            if sources is None or derived is None:
                raise ValueError("autotune measurement needs a sample batch")
            _bench = default_bench(
                partition,
                mesh,
                row_axis=row_axis,
                col_axis=col_axis,
                replica_axis=replica_axis,
                sources=sources,
                derived=derived,
                hybrid_threshold=hybrid_threshold,
                clock=clock,
            )
        return _bench

    def cost_of(cand: Candidate) -> float | None:
        """Measured per-level seconds of ``cand``: cache hit, else (in
        "measure" mode) a fresh micro-bench recorded under measure-once
        keys; None in "cache" mode on a miss (roofline fallback)."""
        ckey = cand.key()
        rec = cache.get(gkey, ckey)
        if rec is not None:
            plan.hits += 1
            return rec.level_s
        plan.misses += 1
        if mode != "measure":
            return None
        rec = get_bench()(cand)
        cache.put(gkey, ckey, rec)
        plan.measured += 1
        logger.info(
            "autotune measured %s @ %s: %.3es/level (walls %s)",
            ckey, gkey, rec.level_s, [f"{w:.3e}" for w in rec.walls],
        )
        return rec.level_s

    # ---- stage 1: BCSR tile shape (tiled engines, tile not forced) ----
    tiled = engine_kind in TILED_ENGINES
    if tile is not None:
        plan.tile, plan.tile_source = tile, "explicit"
    elif tiled:
        cands = partition.tile_candidates()
        costs = {t: cost_of(Candidate("pallas_sparse", "none", batch_size, t))
                 for t in cands}
        measured = {t: c for t, c in costs.items() if c is not None}
        if measured:
            plan.tile = min(measured, key=measured.get)
            plan.tile_source = "measured"
        else:
            plan.tile = _roofline_tile(partition, batch_size, cands)
            plan.tile_source = "roofline"

    # ---- stage 2: hybrid dense/sparse calibration --------------------
    if engine_kind == "pallas_hybrid":
        dense_s = cost_of(Candidate("pallas", "none", batch_size, None))
        sparse_s = cost_of(Candidate("pallas_sparse", "none", batch_size, plan.tile))
        if dense_s is not None and sparse_s is not None:
            plan.cell_costs = (dense_s, sparse_s)

    # ---- stage 3: overlap policies on the final engine/tile ----------
    policies = (
        list(OVERLAP_POLICIES) if overlap == "auto" else [normalize_overlap(overlap)]
    )
    for policy in policies:
        c = cost_of(Candidate(engine_kind, policy, batch_size, plan.tile))
        if c is not None:
            plan.overlap_level_s[policy] = c
    return plan


def _roofline_tile(partition, batch_size, candidates):
    """Roofline fallback for the tile pick: price each candidate's
    compute term and take the cheapest (lazy import — see module)."""
    from repro.core.distributed import level_time_estimates

    def price(t):
        compute_s, _, _ = level_time_estimates(
            partition, "pallas_sparse", batch_size, bm=t[0], bk=t[1]
        )
        return compute_s

    return min(candidates, key=price) if candidates else None
