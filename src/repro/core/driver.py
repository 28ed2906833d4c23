"""The driver layer: one round body and one host round loop for all
engines (single-device dense/sparse/Pallas and 2-D distributed).

:func:`traversal_round` is the per-round algebra — forward counting,
2-degree column derivation, dependency accumulation, per-round BC and
component-size (n_s) extraction, plus the round's own traversal depth
(the straggler scheduler's cost signal) — written once against the
:class:`repro.core.operators.TraversalOperator` protocol.  Entry points
wrap it in whatever jit/shard_map shell their operator needs.

:class:`BCDriver` is the host loop shared by
:func:`repro.core.bc.betweenness_centrality`,
:func:`repro.core.distributed.distributed_betweenness_centrality`, the
``repro.launch.bc`` CLI and the benchmarks:

* rounds are dealt in *dispatch blocks* of ``rounds_per_dispatch``
  (1 on a single device; the sub-cluster count ``fr`` on a mesh);
* dispatch is asynchronous: up to ``max_inflight`` blocks are in flight
  and ``device_get`` happens only at block boundaries, so host sync no
  longer serializes rounds;
* the BC accumulator lives on device and is *donated* through a jitted
  add (no per-round host round-trip, no per-round buffer copy); it is
  fetched exactly once, after the last round;
* an optional :class:`repro.distributed.fault_tolerance.RoundLedger`
  makes the loop restartable: committed rounds are re-dealt as inert
  all-padding columns (BC accumulation is additive, padding contributes
  exactly zero), which keeps every dispatch shape static;
* ``straggler`` selects the multi-ledger sub-cluster scheduling policy
  (:data:`STRAGGLER_POLICIES`): with ``"steal"`` or ``"redeal"`` the
  driver keeps one :class:`RoundLedger` *per replica*, tracks a
  per-replica EWMA of per-round wall time (seeded from the roofline's
  ``overlap_step_time`` estimate before any round completes), and moves
  uncommitted rounds between replica queues when one replica straggles.
  Commits then move from dispatch time to drain time and the BC
  accumulate is masked by the commit outcome, so a round dispatched on
  two replicas (speculative tail duplication, or a re-deal racing a
  kill-and-resume) is accumulated exactly once: first commit wins, the
  loser's lane is multiplied by zero *before* the donated add.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import time
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.spans import attach_run, span
from repro.core.heuristics.one_degree import OneDegreeReduction, leaf_correction
from repro.core.heuristics.two_degree import derive_two_degree_columns
from repro.core.operators import TraversalOperator, as_operator
from repro.core.scheduler import Schedule, redeal_rounds, split_rounds

__all__ = [
    "BCResult",
    "BCDriver",
    "traversal_round",
    "apply_reduction_corrections",
    "STRAGGLER_POLICIES",
    "normalize_straggler",
    "INTEGRITY_MODES",
    "normalize_integrity",
    "DEFAULT_MAX_RETRIES",
    "DEFAULT_RETRY_BACKOFF_S",
]

logger = logging.getLogger(__name__)

#: Sub-cluster straggler-mitigation policies of :class:`BCDriver` (the
#: single source of truth for ``--straggler`` choices and the docs drift
#: check).  ``"none"`` keeps the static deal (one shared ledger, commits
#: at dispatch — the legacy loop).  ``"steal"`` is the conservative
#: multi-ledger policy: work moves only when a replica's queue runs dry —
#: the idle replica pulls the next round from the heaviest backlog, and
#: at the very tail it speculatively *duplicates* the presumed
#: straggler's in-flight round instead of dispatching padding (MapReduce
#: backup tasks; first commit wins).  ``"redeal"`` is the aggressive
#: policy: when a replica's EWMA per-round wall exceeds
#: ``straggler_factor ×`` the fastest replica's, every pending round is
#: re-dealt across the replica queues so similar-cost rounds are
#: co-scheduled (the straggler's backlog drains into the fastest
#: replica's queue).
STRAGGLER_POLICIES = ("none", "steal", "redeal")

_EWMA_ALPHA = 0.5  # weight of the newest per-round wall observation

#: Self-healing defaults: re-dispatches allowed per block (transient
#: errors and quarantined non-finite outputs share the budget) and the
#: base of the exponential backoff between transient retries.  2 retries
#: rides out the one-off XLA hiccups worth retrying; anything persisting
#: past that is a real failure the fallback/caller must see.
DEFAULT_MAX_RETRIES = 2
DEFAULT_RETRY_BACKOFF_S = 0.05

#: Round-integrity modes of :class:`BCDriver` (the single source of
#: truth for ``--integrity`` choices and the docs drift check).
#: ``"off"`` accumulates round outputs unaudited (the legacy behaviour).
#: ``"audit"`` makes every round also return an integrity record — a
#: per-lane bc-sum *claim* computed inside the round — and the driver
#: audits each block host-side at the per-block sync: claim vs the
#: recomputed lane sum (in-transit corruption), BC non-negativity, level
#: and component-size bounds; under ``straggler="steal"`` the
#: speculative duplicate lanes additionally *vote* — digests compared,
#: mismatches quarantined and re-dispatched as a tie-breaker.
#: ``"checksum"`` adds the ABFT ones-checksum lane to every forward and
#: backward SpMM (operators.*_level_checked), carrying the max relative
#: column-sum residual in the record, so in-SpMM corruption is caught
#: the moment it happens — the strongest (and costliest: one extra lane
#: per product) mode.
INTEGRITY_MODES = ("off", "audit", "checksum")

#: ABFT residual threshold: healthy f32 reductions land around 1e-6
#: relative; 1e-3 keeps ~3 orders of magnitude of slack against
#: accumulation-order noise while still catching any corruption that
#: could move BC beyond parity tolerance.
CHECKSUM_TOL = 1e-3
#: Relative tolerance for the bc-sum claim audit (in-round claim vs the
#: host-recomputed lane sum — both f32 reductions in different orders).
CLAIM_RTOL = 1e-4
#: Relative tolerance for the duplicate-vote digest compare: both lanes
#: ran the identical deterministic computation, so any real divergence
#: is corruption.
VOTE_RTOL = 1e-6


def normalize_integrity(mode: str | None) -> str:
    """Validate an integrity mode string (None means "off")."""
    mode = "off" if mode is None else mode
    if mode not in INTEGRITY_MODES:
        raise ValueError(
            f"unknown integrity mode {mode!r}; expected one of {INTEGRITY_MODES}"
        )
    return mode


def normalize_straggler(policy: str | None) -> str:
    """Validate a straggler policy string (None means "none")."""
    policy = "none" if policy is None else policy
    if policy not in STRAGGLER_POLICIES:
        raise ValueError(
            f"unknown straggler policy {policy!r}; expected one of "
            f"{STRAGGLER_POLICIES}"
        )
    return policy


def _append_derived(sigma, depth, sources, derived, row_ids):
    """The backward state and the root of every column: the explicit
    columns, then the 2-degree columns derived from them (Alg. 7).

    ``derived`` of k = 0 rows (a static shape: the schedule claimed no
    2-degree vertex) leaves the forward state as it is, ``[n, s]`` wide.
    """
    if derived.shape[0] == 0:
        return sigma, depth, sources
    sigma_c, depth_c = derive_two_degree_columns(
        sigma, depth, derived, row_ids=row_ids
    )
    return (
        jnp.concatenate([sigma, sigma_c], axis=1),
        jnp.concatenate([depth, depth_c], axis=1),
        jnp.concatenate([sources, derived[:, 0]]),
    )


def traversal_round(
    operator: TraversalOperator,
    sources: jnp.ndarray,  # i32 [s]; -1 = padding
    derived: jnp.ndarray,  # i32 [k, 3] rows (c, a_pos, b_pos); -1 = padding
    omega: jnp.ndarray,  # f32 [n_rows] 1-degree weights (operator's rows)
    *,
    num_levels: int | None = None,
    integrity: str = "off",
) -> tuple[jnp.ndarray, ...]:
    """One BC round against the operator protocol.

    Returns
      bc_local  f32 [n_rows] — this round's BC contribution to the
                operator's rows (global BC = sum over rounds/devices),
      ns        f32 [s+k]    — per-column component size n_s (§3.4.1),
                already globally reduced,
      roots     i32 [s+k]    — root vertex of every column (-1 padding),
      levels    i32 []       — traversal depth of *this* round on its own
                grid (``reduce_max_grid``: per-replica even when
                ``sync_axes`` pins the loop bounds to the mesh-wide max).
                0 for an all-padding round.  This is the data-dependent
                cost signal the straggler scheduler attributes wall time
                by.

    With ``integrity != "off"`` (see :data:`INTEGRITY_MODES`) a fifth
    element is returned: ``integ`` f32 [2] = ``[err, claim]`` — the
    round's max ABFT checksum residual (0 in "audit" mode, where the
    checked level steps don't run) and the round's own bc-sum claim
    (``Σ bc_local`` over the whole replica, computed *before* the block
    leaves the device, so the driver can detect corruption in transit
    or in the accumulate path).
    """
    integrity = normalize_integrity(integrity)
    checksum = integrity == "checksum"
    op = as_operator(operator)
    if getattr(op, "weighted", False):
        return _weighted_round(
            op, sources, derived, omega, num_levels=num_levels, integrity=integrity
        )
    omega_f = omega.astype(jnp.float32)
    row_ids = op.row_ids()

    # ---------------------------------------------------------- forward
    src_onehot = (
        (row_ids[:, None] == sources[None, :]) & (sources[None, :] >= 0)
    ).astype(jnp.float32)
    fwd = engine.forward_counting(
        op, src_onehot, num_levels=num_levels, checksum=checksum
    )

    # ------------------------------------------- derived 2-degree columns
    sigma_all, depth_all, roots = _append_derived(
        fwd.sigma, fwd.depth, sources, derived, row_ids
    )

    # ---------------------------------------------------------- backward
    # decomposed max: grid first (the per-replica depth = the straggler
    # cost signal), then the sync-axes extension for the loop bound — one
    # reduction total when sync_axes is empty (reduce_max_sync is a no-op)
    grid_max = op.reduce_max_grid(jnp.max(depth_all))
    max_depth = op.reduce_max_sync(grid_max)
    bwd = engine.backward_accumulation(
        op,
        sigma_all,
        depth_all,
        omega_f,
        max_depth,
        num_levels=num_levels,
        checksum=checksum,
    )
    delta, bwd_err = bwd if checksum else (bwd, None)

    # --------------------------------------------------------- BC + n_s
    omega_root = op.root_omega(roots, omega_f)
    mult = jnp.where(roots >= 0, omega_root + 1.0, 0.0)

    root_onehot = row_ids[:, None] == roots[None, :]
    weighted = jnp.where(root_onehot, 0.0, delta * mult[None, :])
    bc_local = weighted.sum(axis=1)

    # per-column component size  n_s = Σ_{d ≥ 0} (1 + ω)   (paper §3.4.1)
    ns = op.reduce_sum(((depth_all >= 0) * (1.0 + omega_f)[:, None]).sum(axis=0))
    levels = (grid_max + 1).astype(jnp.int32)
    if integrity == "off":
        return bc_local, ns, roots, levels
    # [err, claim]: the replica's max ABFT residual (grid-agreed, so it
    # is replicated like ns) and its own bc-sum claim.  Both are f32
    # scalars computed before the block crosses the device boundary.
    claim = op.reduce_sum(jnp.sum(bc_local))
    if checksum:
        err = op.reduce_max_grid(jnp.maximum(fwd.check_err, bwd_err))
    else:
        err = jnp.float32(0.0)
    integ = jnp.stack(
        [jnp.asarray(err, jnp.float32), jnp.asarray(claim, jnp.float32)]
    )
    return bc_local, ns, roots, levels, integ


def _weighted_round(
    op,
    sources: jnp.ndarray,
    derived: jnp.ndarray,
    omega: jnp.ndarray,
    *,
    num_levels: int | None,
    integrity: str,
) -> tuple[jnp.ndarray, ...]:
    """One *weighted* BC round: the bucket-loop analogue of
    :func:`traversal_round`, same return contract.

    The round's ``levels`` slot carries the bucket count (the same
    data-dependent cost signal the straggler scheduler consumes).  The
    2-degree derivation is level-based and is rejected upstream for
    weighted runs, so ``derived`` is empty (k = 0) or all-padding here —
    padding columns stay shape-compatible and inert.  ``num_levels``
    (the static-trip-count dry-run mode) has no weighted analogue: the
    bucket loop's trip count is data-dependent by construction.
    """
    if num_levels is not None:
        raise ValueError(
            "num_levels (static trip count) is not supported for weighted "
            "traversal: the bucket loop's trip count is data-dependent"
        )
    if integrity == "checksum":
        raise ValueError(
            "integrity='checksum' (ABFT level checksums) is level-"
            "synchronous and not supported for weighted traversal; use "
            "integrity='audit'"
        )
    omega_f = omega.astype(jnp.float32)
    row_ids = op.row_ids()

    src_onehot = (
        (row_ids[:, None] == sources[None, :]) & (sources[None, :] >= 0)
    ).astype(jnp.float32)
    fwd = engine.forward_buckets(op, src_onehot)

    # bucket index per (vertex, column): the weighted depth structure
    from repro.kernels.ops import bucket_index

    bucket = bucket_index(fwd.dist, op.delta)

    # derived columns: always padding under weighted (h2/h3 rejected
    # upstream) — kept for shape compatibility with the driver contract
    _, bucket_all, roots = _append_derived(
        fwd.sigma, bucket, sources, derived, row_ids
    )

    grid_max = op.reduce_max_grid(jnp.max(bucket_all))
    max_bucket = op.reduce_max_sync(grid_max)
    delta_acc = engine.backward_buckets(op, fwd.sigma, fwd.dist, omega_f, max_bucket)
    delta_all = jnp.pad(delta_acc, ((0, 0), (0, derived.shape[0])))

    omega_root = op.root_omega(roots, omega_f)
    mult = jnp.where(roots >= 0, omega_root + 1.0, 0.0)

    root_onehot = row_ids[:, None] == roots[None, :]
    contrib = jnp.where(root_onehot, 0.0, delta_all * mult[None, :])
    bc_local = contrib.sum(axis=1)

    ns = op.reduce_sum(((bucket_all >= 0) * (1.0 + omega_f)[:, None]).sum(axis=0))
    levels = (grid_max + 1).astype(jnp.int32)
    if integrity == "off":
        return bc_local, ns, roots, levels
    claim = op.reduce_sum(jnp.sum(bc_local))
    integ = jnp.stack(
        [jnp.float32(0.0), jnp.asarray(claim, jnp.float32)]
    )
    return bc_local, ns, roots, levels, integ


def apply_reduction_corrections(
    bc: np.ndarray,
    prep: OneDegreeReduction,
    schedule,
    ns_by_root: dict[int, float],
) -> None:
    """Add the analytic BC credits of the 1-degree/tree reduction.

    Every vertex x with removed branches (S(x) > 0) — residual or removed
    interior — gets 2·S·(n_comp−1−S) + 2·P (heuristics/one_degree.py).
    n_comp comes from x's own round, the isolated-residual analytic size,
    or (removed vertices) the resolved root's size."""
    n_by_root = dict(ns_by_root)
    for v, n_comp in schedule.analytic_corrections:
        n_by_root[int(v)] = float(n_comp)
    S, P = prep.omega, prep.pair_credit
    for x in np.nonzero(S > 0)[0]:
        x = int(x)
        if prep.removed[x]:
            root, analytic_n = prep.resolve_root(x)
            n_comp = analytic_n if analytic_n >= 0 else n_by_root.get(int(root))
        else:
            n_comp = n_by_root.get(x)
        if n_comp is None:
            raise RuntimeError(f"no component size recorded for vertex {x}")
        bc[x] += leaf_correction(S[x], n_comp, P[x])


@dataclasses.dataclass
class BCResult:
    bc: np.ndarray  # float64 [n]
    schedule: Schedule
    rounds_run: int
    forward_columns: int  # explicit BFS columns actually traversed
    backward_columns: int  # dependency columns (explicit + derived)
    wall_s: float = 0.0  # seconds of the round loop (the bc.driver.run span)
    block_times: list[float] | None = None  # seconds of each dispatch
    #   block (its bc.driver.block span).  They time the device only where
    #   the loop waits for each block — profile mode, straggler mode or a
    #   stop rule; otherwise dispatch runs ahead and they time the host.
    straggler_stats: dict | None = None  # multi-ledger scheduler telemetry
    #   (straggler != "none" only): per-replica wall/rounds/levels,
    #   rounds stolen / re-dealt, speculative duplicates, idle estimate.
    stopped_early: bool = False  # a stop_rule halted dispatch before the
    #   schedule was exhausted (adaptive sampling / serving refresh
    #   slices); the bc accumulator holds exactly the committed prefix
    stop_stats: dict | None = None  # the stop rule's own telemetry
    #   (rule.stats when it has one): checks, stability history,
    #   fired_at_block
    roots_accumulated: int = 0  # root columns (explicit + derived) of
    #   every committed round, including rounds resumed from a
    #   checkpoint — the k in the sampled estimator's N/k rescale
    sampling_stats: dict | None = None  # set by the entrypoints when
    #   sampling != "off": mode, k planned, eligible count, applied scale
    recovery_stats: dict | None = None  # self-healing telemetry (always
    #   set by BCDriver): retries, transient_errors, quarantined_blocks,
    #   fallback_recomputes, remesh_events, dead_replicas,
    #   resumed_generation (BCCheckpoint generation the run resumed
    #   from; None = cold start / no checkpoint), plus the "integrity"
    #   sub-dict (mode, checksum/audit failures, max residual, duplicate
    #   votes + verdicts, quarantined rounds, watchdog trips /
    #   re-dispatches / escalations).
    spans: tuple | None = None  # the run's host spans (repro.core.spans),
    #   root first; set by whichever call opened the run's root span


def _unpack_block(out):
    """Normalize a round_fn output to the 5-tuple
    ``(bc, ns, roots, levels, integ)`` — legacy 3-tuples (no levels) and
    4-tuples (no integrity record) get ``None`` in the missing slots."""
    if len(out) == 5:
        return tuple(out)
    if len(out) == 4:
        return tuple(out) + (None,)
    bc_blk, ns, roots = out
    return bc_blk, ns, roots, None, None


class BCDriver:
    """Shared host round loop (see module docstring).

    ``round_fn(sources i32 [fr, s], derived i32 [fr, k, 3])`` must return
    device arrays ``(bc_block, ns [fr, s+k], roots [fr, s+k],
    levels [fr])`` where ``bc_block`` has any stable shape whose leading
    dims sum away to the per-vertex contribution ([n] on one device;
    [fr, n_pad] sharded on a mesh).  All graph-constant operands
    (adjacency, ω, arc lists) are expected to be partially applied into
    ``round_fn``.  Legacy 3-tuple round functions (no ``levels``) are
    accepted under ``straggler="none"``.

    ``profile=True`` waits for every dispatch block inside its
    ``bc.driver.block`` span, so ``BCResult.block_times`` times the
    device — the measurement mode the overlap benchmarks use; it defeats
    the async pipeline, so leave it off in production.

    Host spans (:mod:`repro.core.spans`): ``bc.driver.run`` around the
    loop (``BCResult.wall_s``); per dispatch block ``bc.driver.block``
    (``BCResult.block_times``) holding ``bc.driver.dispatch`` (sources to
    the device, the round call, the accumulate), ``bc.driver.drain``
    (the wait for a block, then its bookkeeping; in the straggler loop
    the wait alone), ``bc.driver.collect`` (accumulator to the host),
    ``bc.driver.stop_rule`` and ``bc.driver.checkpoint``; ``block``
    attributes name the block.

    ``straggler`` (see :data:`STRAGGLER_POLICIES`) enables the
    multi-ledger sub-cluster scheduler; it requires ``round_fn`` to carry
    a leading replica dim of ``rounds_per_dispatch`` on ``bc_block`` and
    to return ``levels``, and — like ``profile`` — blocks per dispatch
    block (the per-round wall observations are its control signal).
    ``straggler_factor`` is the EWMA ratio that flags a replica as a
    straggler; ``prior_round_s`` seeds every replica's EWMA before any
    round completes (callers pass the roofline ``overlap_step_time``
    estimate — or, under ``autotune``, the measured per-level cost via
    :func:`repro.core.distributed.prior_round_seconds` — symmetric, so
    no re-deal can fire on the prior alone).  ``round_costs`` hands the
    static deal a per-round cost prior (``Schedule.round_depths``): the
    initial queues then pack similar-cost rounds per dispatch block
    instead of interleaving by id.

    **Self-healing** (telemetry in ``BCResult.recovery_stats``):
    transient round failures are retried in place (``max_retries``
    re-dispatches, exponential backoff from ``retry_backoff_s``); the
    numeric guard (``numeric_guard``, auto-on wherever the loop already
    syncs per block) quarantines non-finite bc/ns blocks and re-runs
    them, escalating to ``fallback_round_fn`` — the caller's known-good
    dense path — when the corruption persists; under ``straggler ≠
    "none"`` a :class:`repro.distributed.fault_tolerance.
    ReplicaLostError` from the round_fn triggers an elastic re-mesh
    (``plan_elastic_remesh`` over ``mesh_shape``/``mesh_axes``): the
    dead replica's ledger merges into a survivor's, its backlog is
    re-dealt, and the loop continues at reduced effective ``fr`` with
    the dead lane dealt only padding.
    """

    def __init__(
        self,
        round_fn: Callable,
        schedule: Schedule,
        *,
        n: int,
        prep: OneDegreeReduction | None = None,
        ledger=None,
        checkpoint=None,
        checkpoint_every: int = 8,
        rounds_per_dispatch: int = 1,
        max_inflight: int = 2,
        profile: bool = False,
        straggler: str = "none",
        straggler_factor: float = 2.0,
        prior_round_s: float | None = None,
        round_costs=None,
        max_retries: int = DEFAULT_MAX_RETRIES,
        retry_backoff_s: float = DEFAULT_RETRY_BACKOFF_S,
        numeric_guard: bool | None = None,
        fallback_round_fn: Callable | None = None,
        mesh_shape: tuple[int, ...] | None = None,
        mesh_axes: tuple[str, ...] | None = None,
        integrity: str = "off",
        dispatch_deadline_s: float | None = None,
        clock: Callable[[], float] | None = None,
        sleeper: Callable[[float], None] | None = None,
        stop_rule: Callable[[np.ndarray, int], bool] | None = None,
        level_bound: int | None = None,
    ):
        self.round_fn = round_fn
        #: integrity-audit upper bound on a round's reported traversal
        #: depth.  None = the unweighted structural bound (n + 1 levels).
        #: Weighted callers pass their bucket-count bound — bucket indices
        #: scale with (max distance / Δ), not with n.
        self.level_bound = level_bound
        self.profile = profile
        #: the early-stop seam (repro.serving): a callable
        #: ``(bc_running f64 [n], blocks_done) -> bool`` consulted after
        #: every drained dispatch block — True halts *new* dispatches;
        #: everything already committed stays committed (checkpoints,
        #: chaos and the straggler re-deal compose unchanged because the
        #: consult sits outside the dispatch/commit machinery).  Note the
        #: consult syncs the accumulator to host each block, so it costs
        #: the async static pipeline — adaptive sampling opts in.
        self.stop_rule = stop_rule
        self.schedule = schedule
        self.n = n
        self.prep = prep
        self.checkpoint = checkpoint
        self.checkpoint_every = max(1, checkpoint_every)
        self.straggler = normalize_straggler(straggler)
        self.straggler_factor = float(straggler_factor)
        self.prior_round_s = prior_round_s
        #: per-round expected cost (Schedule.round_depths when the
        #: scheduler packed by eccentricity) — seeds the straggler deal
        #: (split_rounds round_costs) so lanes start cost-balanced
        self.round_costs = round_costs
        self._bc0 = np.zeros(n, np.float64)
        self._ns0: dict[int, float] = {}
        self._fingerprint = None
        self.fr = max(1, rounds_per_dispatch)
        self.max_inflight = max(1, max_inflight)

        # ------------------------------------------------- self-healing
        self.max_retries = max(0, int(max_retries))
        self.retry_backoff_s = float(retry_backoff_s)
        self.fallback_round_fn = fallback_round_fn
        # The guard fetches a per-block finiteness bit, i.e. a host sync.
        # Auto-resolution turns it on exactly where that sync is already
        # paid (profile / straggler modes block per dispatch to measure)
        # or where the caller opted into recovery (a fallback round_fn);
        # the pure-async static fast path stays unsynced unless asked.
        if numeric_guard is None:
            numeric_guard = (
                fallback_round_fn is not None
                or self.straggler != "none"
                or profile
            )
        self.numeric_guard = bool(numeric_guard)
        # mesh geometry for plan_elastic_remesh on replica loss: the
        # replica ('pod') axis is the dispatch lane dim by default;
        # distributed callers pass the true (fr, R, C) shape.
        self.mesh_shape = tuple(mesh_shape) if mesh_shape is not None else (self.fr,)
        self.mesh_axes = tuple(mesh_axes) if mesh_axes is not None else ("pod",)
        self._dead_lanes: set[int] = set()
        self.recovery: dict = {
            "retries": 0,
            "transient_errors": 0,
            "quarantined_blocks": 0,
            "fallback_recomputes": 0,
            "remesh_events": 0,
            "dead_replicas": [],
            "resumed_generation": None,
        }
        # ---------------------------------------------------- integrity
        self.integrity = normalize_integrity(integrity)
        if dispatch_deadline_s is not None and float(dispatch_deadline_s) <= 0:
            raise ValueError(
                f"dispatch_deadline_s must be positive, got {dispatch_deadline_s}"
            )
        self.dispatch_deadline_s = (
            None if dispatch_deadline_s is None else float(dispatch_deadline_s)
        )
        # injectable time sources: the watchdog measures the dispatch
        # call window through ``clock`` and the retry backoff sleeps
        # through ``sleeper``, so chaos/watchdog tests drive both with
        # fakes instead of burning wall-clock
        self._clock = clock if clock is not None else time.monotonic
        self._sleep = sleeper if sleeper is not None else time.sleep
        self.recovery["integrity"] = {
            "mode": self.integrity,
            "checksum_failures": 0,
            "audit_failures": 0,
            "max_checksum_residual": 0.0,
            "votes": 0,
            "vote_mismatches": 0,
            "vote_verdicts": [],
            "quarantined_rounds": 0,
            "watchdog_trips": 0,
            "watchdog_redispatches": 0,
            "watchdog_escalations": 0,
        }
        #: rid -> {"owner": digest, "duplicate": digest} for rounds whose
        #: duplicate vote disagreed; resolved (verdict recorded) when the
        #: tie-breaker re-dispatch commits cleanly
        self._pending_votes: dict[int, dict] = {}
        self._finite_check = jax.jit(
            lambda bc, ns: jnp.isfinite(bc).all() & jnp.isfinite(ns).all()
        )
        # per-lane bc digests for the claim audit and the duplicate vote:
        # (lane sums, global min, global max) in one fetch
        self._block_digest = jax.jit(
            lambda bc: (
                bc.reshape(bc.shape[0], -1).sum(axis=1)
                if bc.ndim > 1
                else bc.sum()[None],
                bc.min(),
                bc.max(),
            )
        )

        from repro.distributed.fault_tolerance import (
            RoundLedger,
            schedule_fingerprint,
        )

        if checkpoint is not None:
            if ledger is not None:
                raise ValueError("pass either a ledger or a checkpoint, not both")
            self._fingerprint = schedule_fingerprint(n, schedule)

        if self.straggler != "none":
            if ledger is not None:
                raise ValueError(
                    "straggler scheduling keeps one ledger per replica; "
                    "pass a checkpoint (or nothing), not an external ledger"
                )
            by_lane: list[list[int]] = [[] for _ in range(self.fr)]
            if checkpoint is not None:
                bc0, ns0, stored = checkpoint.load_namespaced(self._fingerprint)
                if bc0 is not None:
                    self._bc0 = bc0[: n]
                    self._ns0 = ns0
                if len(stored) == self.fr:
                    by_lane = [list(lane) for lane in stored]
                else:  # replica count changed across the resume: merge
                    union = sorted({rid for lane in stored for rid in lane})
                    by_lane[0] = union
            self.ledgers = [RoundLedger.from_state(lane) for lane in by_lane]
            self.ledger = None
        else:
            if checkpoint is not None:
                bc0, ns0, committed = checkpoint.load(self._fingerprint)
                if bc0 is not None:
                    self._bc0 = bc0[: n]
                    self._ns0 = ns0
                ledger = RoundLedger.from_state(committed)
            self.ledger = ledger
            self.ledgers = None
        if checkpoint is not None:
            gen = getattr(checkpoint, "loaded_generation", None)
            self.recovery["resumed_generation"] = gen
            if gen is not None:
                (logger.warning if gen > 0 else logger.info)(
                    "resumed from checkpoint generation %d%s",
                    gen,
                    " (newer snapshots were corrupt)" if gen > 0 else "",
                )
            # resume the recovery telemetry the snapshot carried, so a
            # kill-and-resume keeps its retry/quarantine/re-mesh history
            # instead of resetting the counters to zero
            stored = getattr(checkpoint, "loaded_stats", None)
            if stored:
                for key in (
                    "retries",
                    "transient_errors",
                    "quarantined_blocks",
                    "fallback_recomputes",
                    "remesh_events",
                ):
                    self.recovery[key] = int(stored.get(key, 0))
                sint = stored.get("integrity") or {}
                ist = self.recovery["integrity"]
                for key in list(ist):
                    if key == "mode":
                        continue
                    if key == "vote_verdicts":
                        ist[key] = list(sint.get(key, []))
                    elif key == "max_checksum_residual":
                        ist[key] = float(sint.get(key, 0.0))
                    else:
                        ist[key] = int(sint.get(key, 0))
        # donated device-side accumulate: bc never round-trips per round
        self._accumulate = jax.jit(lambda acc, x: acc + x, donate_argnums=(0,))
        # drain-time masked accumulate (straggler modes): the commit
        # outcome zeroes losing lanes *before* the donated add, so a
        # double-dispatched round contributes exactly once.
        def _bmask(blk, m):
            return blk * m.reshape(m.shape + (1,) * (blk.ndim - 1))

        self._masked_accumulate = jax.jit(
            lambda acc, blk, m: acc + _bmask(blk, m), donate_argnums=(0,)
        )
        self._masked_scale = jax.jit(_bmask)

    # ---------------------------------------------------- self-healing
    def _stats_state(self) -> dict:
        """JSON-serializable recovery telemetry for the checkpoint."""
        out = {
            k: (list(v) if isinstance(v, list) else v)
            for k, v in self.recovery.items()
            if k not in ("resumed_generation", "integrity")
        }
        ist = self.recovery["integrity"]
        out["integrity"] = {
            k: (list(v) if isinstance(v, list) else v) for k, v in ist.items()
        }
        return out

    def _integrity_audit(self, out) -> str | None:
        """Audit one block's output; return a failure reason or None.

        Host-side, at a point where the loop already syncs (the audit
        itself fetches the block digest).  Checks, in order: the ABFT
        checksum residual carried in the integrity record ("checksum"
        mode), the per-lane bc-sum claim vs the recomputed lane digest,
        BC non-negativity, and the level / component-size output-domain
        bounds.  Every check is O(fr + s) host work on already-reduced
        scalars — the O(n·s) work stayed on device.
        """
        bc_blk, ns, roots, levels, integ = out
        ist = self.recovery["integrity"]
        sums_dev, mn_dev, mx_dev = self._block_digest(bc_blk)
        sums = np.asarray(jax.device_get(sums_dev), np.float64).reshape(-1)
        mn = float(jax.device_get(mn_dev))
        scale = max(1.0, float(np.abs(sums).max()))
        if integ is not None:
            ig = np.asarray(jax.device_get(integ), np.float64).reshape(-1, 2)
            resid = float(ig[:, 0].max())
            ist["max_checksum_residual"] = max(
                ist["max_checksum_residual"], resid
            )
            if resid > CHECKSUM_TOL:
                return (
                    f"ABFT checksum residual {resid:.3e} exceeds "
                    f"{CHECKSUM_TOL:g}"
                )
            claims = ig[:, 1]
            if claims.shape[0] == sums.shape[0]:
                diff = float(np.abs(claims - sums).max())
                if diff > CLAIM_RTOL * scale:
                    return (
                        f"bc-sum claim mismatch: |claim - sum| = {diff:.3e} "
                        f"(scale {scale:.3e})"
                    )
        if mn < -CLAIM_RTOL * scale:
            return f"negative BC contribution (min {mn:.3e})"
        if levels is not None:
            lv = np.asarray(jax.device_get(levels)).reshape(-1)
            bound = self.level_bound if self.level_bound is not None else self.n + 1
            if lv.min() < 0 or lv.max() > bound:
                return f"level bound violation (levels {lv.tolist()})"
        ns_np = np.asarray(jax.device_get(ns), np.float64)
        ns_max = float(ns_np.max()) if ns_np.size else 0.0
        if ns_max > self.n * (1.0 + 1e-5) + 1e-6:
            return f"component size {ns_max:.6g} exceeds n = {self.n}"
        return None

    def _dispatch_block(self, srcs, ders):
        """Run ``round_fn`` on one dispatch block with recovery.

        Transient failures (:func:`repro.distributed.fault_tolerance.
        is_transient_error`) are retried in place with exponential
        backoff, up to ``max_retries`` re-dispatches per block.  A
        ``dispatch_deadline_s`` arms the **watchdog**: a dispatch call
        that returns only after the deadline is treated as a wedged
        collective — re-dispatched from the retry budget, then escalated
        as :class:`ReplicaLostError` so the multi-ledger loop re-meshes
        around the suspect replica (the static loop propagates it — it
        has no spare lanes to absorb a loss).  Under the numeric guard a
        block whose bc/ns came back non-finite is *quarantined* — never
        accumulated — and re-dispatched from the same budget; if the
        poison persists the block is recomputed via ``fallback_round_fn``
        (the caller's known-good dense path) with a fresh budget.
        ``integrity != "off"`` runs :meth:`_integrity_audit` on every
        block with the identical quarantine → re-dispatch → fallback →
        raise ladder (terminal error:
        :class:`repro.distributed.fault_tolerance.IntegrityError`).
        :class:`ReplicaLostError` from the round_fn always propagates:
        in-place retry cannot resurrect devices.  Returns the unpacked
        5-tuple.
        """
        from repro.distributed.fault_tolerance import (
            IntegrityError,
            ReplicaLostError,
            is_transient_error,
        )

        srcs_dev = jnp.asarray(srcs)
        ders_dev = jnp.asarray(ders)
        fn = self.round_fn
        attempt = 0
        while True:
            try:
                t0 = self._clock()
                out = _unpack_block(fn(srcs_dev, ders_dev))
                if self.dispatch_deadline_s is not None:
                    # measure to completion of the dispatched values: the
                    # deadline covers a wedged collective inside the call
                    jax.block_until_ready(out[0])
                elapsed = self._clock() - t0
            except Exception as e:
                if is_transient_error(e) and attempt < self.max_retries:
                    backoff = self.retry_backoff_s * (2.0 ** attempt)
                    self.recovery["transient_errors"] += 1
                    self.recovery["retries"] += 1
                    logger.warning(
                        "transient round failure (%s: %s); retry %d/%d "
                        "after %.3fs backoff",
                        type(e).__name__, e, attempt + 1, self.max_retries,
                        backoff,
                    )
                    self._sleep(backoff)
                    attempt += 1
                    continue
                raise
            if (
                self.dispatch_deadline_s is not None
                and elapsed > self.dispatch_deadline_s
            ):
                ist = self.recovery["integrity"]
                ist["watchdog_trips"] += 1
                if attempt < self.max_retries:
                    ist["watchdog_redispatches"] += 1
                    self.recovery["retries"] += 1
                    logger.warning(
                        "dispatch watchdog: block took %.3fs > deadline "
                        "%.3fs; re-dispatching (%d/%d)",
                        elapsed, self.dispatch_deadline_s,
                        attempt + 1, self.max_retries,
                    )
                    attempt += 1
                    continue
                ist["watchdog_escalations"] += 1
                raise ReplicaLostError(
                    -1,
                    f"dispatch exceeded its {self.dispatch_deadline_s:.3f}s "
                    f"deadline {attempt + 1} times (last {elapsed:.3f}s); "
                    f"treating a replica as wedged",
                )
            if self.numeric_guard and not bool(
                self._finite_check(out[0], out[1])
            ):
                self.recovery["quarantined_blocks"] += 1
                if attempt < self.max_retries:
                    self.recovery["retries"] += 1
                    logger.warning(
                        "non-finite bc/ns block quarantined; re-dispatching "
                        "(%d/%d)", attempt + 1, self.max_retries,
                    )
                    attempt += 1
                    continue
                if (
                    self.fallback_round_fn is not None
                    and fn is not self.fallback_round_fn
                ):
                    self.recovery["fallback_recomputes"] += 1
                    logger.warning(
                        "non-finite bc/ns block persists after %d "
                        "re-dispatches; recomputing via the fallback "
                        "round_fn", self.max_retries,
                    )
                    fn = self.fallback_round_fn
                    attempt = 0
                    continue
                raise FloatingPointError(
                    f"non-finite bc/ns block output persisted through "
                    f"{self.max_retries} re-dispatches"
                    + (
                        " and the fallback round_fn"
                        if self.fallback_round_fn is not None
                        else " (no fallback_round_fn supplied)"
                    )
                )
            if self.integrity != "off":
                reason = self._integrity_audit(out)
                if reason is not None:
                    ist = self.recovery["integrity"]
                    if "checksum" in reason:
                        ist["checksum_failures"] += 1
                    else:
                        ist["audit_failures"] += 1
                    self.recovery["quarantined_blocks"] += 1
                    if attempt < self.max_retries:
                        self.recovery["retries"] += 1
                        logger.warning(
                            "integrity audit failed (%s); block quarantined, "
                            "re-dispatching (%d/%d)",
                            reason, attempt + 1, self.max_retries,
                        )
                        attempt += 1
                        continue
                    if (
                        self.fallback_round_fn is not None
                        and fn is not self.fallback_round_fn
                    ):
                        self.recovery["fallback_recomputes"] += 1
                        logger.warning(
                            "integrity failure persists after %d "
                            "re-dispatches (%s); recomputing via the "
                            "fallback round_fn", self.max_retries, reason,
                        )
                        fn = self.fallback_round_fn
                        attempt = 0
                        continue
                    raise IntegrityError(
                        f"round block failed its integrity audit ({reason}) "
                        f"through {self.max_retries} re-dispatches"
                        + (
                            " and the fallback round_fn"
                            if self.fallback_round_fn is not None
                            else " (no fallback_round_fn supplied)"
                        )
                    )
            return out

    # ------------------------------------------------------- legacy deal
    def _blocks(self):
        """Deal rounds into [fr]-sized dispatch blocks of host arrays.

        Ledger-committed rounds are dealt as all-padding (-1) columns:
        shapes stay static, contributions are exactly zero, and the
        ledger keeps exactly-once semantics across restarts and
        speculative re-execution (distributed/fault_tolerance.py).
        Rounds are only *read* here — the commit happens at drain time
        (after the block's results exist), so a dispatch that dies never
        strands its rounds as committed-but-never-accumulated in a
        caller-owned ledger.
        """
        s = self.schedule.batch_size
        k = self.schedule.derived_per_round
        rounds = self.schedule.rounds
        for start in range(0, len(rounds), self.fr):
            block = rounds[start : start + self.fr]
            srcs = np.full((self.fr, s), -1, np.int32)
            ders = np.full((self.fr, k, 3), -1, np.int32)
            live = []
            for r, rnd in enumerate(block):
                rid = start + r
                if self.ledger is not None and self.ledger.is_committed(rid):
                    continue  # already accumulated by a previous run
                srcs[r] = rnd.sources
                ders[r] = rnd.derived
                live.append(rid)
            if live:
                yield srcs, ders, live

    def _count_roots(self, rids) -> int:
        """Root columns (explicit + derived) across the given round ids —
        the k of the sampled estimator's N/k rescale, so it must count
        exactly what the accumulator holds: every *committed* round,
        including rounds resumed from a checkpoint."""
        rounds = self.schedule.rounds
        return sum(
            int((rounds[rid].sources >= 0).sum())
            + int((rounds[rid].derived[:, 0] >= 0).sum())
            for rid in rids
        )

    def _collect_bc(self, bc_acc) -> np.ndarray:
        """Checkpoint-seed + device accumulator, in per-vertex f64 space."""
        with span("bc.driver.collect"):
            bc = self._bc0.copy()
            if bc_acc is not None:
                dev = np.asarray(jax.device_get(bc_acc), np.float64)
                if dev.ndim > 1:  # sub-cluster replicas are additive (§3.3)
                    dev = dev.reshape(-1, dev.shape[-1]).sum(axis=0)
                bc = bc + dev[: self.n]
            return bc

    def _finalize(self, bc_acc, ns_by_root) -> np.ndarray:
        bc = self._collect_bc(bc_acc)
        if self.prep is not None:
            apply_reduction_corrections(bc, self.prep, self.schedule, ns_by_root)
        return bc

    def run(self) -> BCResult:
        with span("bc.driver.run") as run_span:
            if self.straggler != "none":
                result = self._run_straggler()
            else:
                result = self._run_static()
        result.wall_s = run_span.seconds
        attach_run(run_span, result)
        return result

    # --------------------------------------------- legacy (static) loop
    def _run_static(self) -> BCResult:
        bc_acc = None
        inflight: collections.deque = collections.deque()
        ns_by_root: dict[int, float] = dict(self._ns0)
        drained: list[int] = self.ledger.state() if self.checkpoint else []
        rounds_run = 0
        fwd_cols = 0
        bwd_cols = 0
        blocks_done = 0
        stopped_early = False
        blocks_since_snapshot = 0
        block_times: list[float] = []

        def drain_one():
            ns_dev, roots_dev, rids, block = inflight.popleft()
            with span("bc.driver.drain", block=block):
                roots_np = np.asarray(roots_dev)  # device_get: block boundary
                ns_np = np.asarray(ns_dev, np.float64)
                for r in range(roots_np.shape[0]):
                    for root, nv in zip(roots_np[r], ns_np[r]):
                        if root >= 0:
                            ns_by_root[int(root)] = float(nv)
                # commit at drain, not dispatch: the round's contribution now
                # exists on device, so a crash before this point re-deals it
                if self.ledger is not None:
                    for rid in rids:
                        self.ledger.try_commit(rid)
                drained.extend(rids)

        def snapshot():
            # drain everything first so (bc, ns, committed) is a
            # consistent prefix — see fault_tolerance.BCCheckpoint.
            with span("bc.driver.checkpoint"):
                while inflight:
                    drain_one()
                self.checkpoint.save(
                    self._collect_bc(bc_acc), ns_by_root, drained, self._fingerprint,
                    stats=self._stats_state(),
                )

        for srcs, ders, live in self._blocks():
            block = blocks_done + 1
            with span("bc.driver.block", block=block) as blk:
                with span("bc.driver.dispatch", block=block):
                    bc_blk, ns, roots, _levels, _integ = self._dispatch_block(srcs, ders)
                    bc_acc = bc_blk if bc_acc is None else self._accumulate(bc_acc, bc_blk)
                if self.profile:  # wait for the block inside its span
                    jax.block_until_ready(bc_acc)
                inflight.append((ns, roots, live, block))
                rounds_run += len(live)
                fwd_cols += int((srcs >= 0).sum())
                bwd_cols += int((srcs >= 0).sum() + (ders[:, :, 0] >= 0).sum())
                while len(inflight) > self.max_inflight:
                    drain_one()
                blocks_done = block
                blocks_since_snapshot += 1
                if self.checkpoint is not None and (
                    blocks_since_snapshot >= self.checkpoint_every
                ):
                    snapshot()
                    blocks_since_snapshot = 0
                if self.stop_rule is not None:
                    # drain first so the accumulator the rule sees is exactly
                    # the committed prefix (what a checkpoint would hold)
                    while inflight:
                        drain_one()
                    bc_now = self._collect_bc(bc_acc)
                    with span("bc.driver.stop_rule", block=block):
                        stopped_early = bool(self.stop_rule(bc_now, blocks_done))
            block_times.append(blk.seconds)
            if stopped_early:
                logger.info(
                    "stop rule fired after %d dispatch blocks "
                    "(%d rounds committed); halting dispatch",
                    blocks_done, len(drained),
                )
                break
        while inflight:
            drain_one()
        if self.checkpoint is not None:
            snapshot()

        return BCResult(
            bc=self._finalize(bc_acc, ns_by_root),
            schedule=self.schedule,
            rounds_run=rounds_run,
            forward_columns=fwd_cols,
            backward_columns=bwd_cols,
            block_times=block_times,
            stopped_early=stopped_early,
            stop_stats=getattr(self.stop_rule, "stats", None),
            roots_accumulated=self._count_roots(drained),
            recovery_stats=dict(self.recovery),
        )

    # ------------------------------------------- multi-ledger scheduler
    def _committed_union(self) -> set[int]:
        out: set[int] = set()
        for led in self.ledgers:
            out |= set(led.state())
        return out

    def _try_commit(self, lane: int, rid: int) -> bool:
        """Exactly-once across *all* replica ledgers (first commit wins)."""
        for led in self.ledgers:
            if led.is_committed(rid):
                return False
        return self.ledgers[lane].try_commit(rid)

    def _run_straggler(self) -> BCResult:
        """The multi-ledger sub-cluster round loop (steal / redeal).

        Differences from the static loop:

        * one round-id queue and one :class:`RoundLedger` per replica,
          seeded by :func:`repro.core.scheduler.split_rounds` minus
          whatever any ledger already committed (merged resume);
        * each dispatch block is *timed* (block_until_ready, as in
          profile mode) and its wall is attributed to the replicas in
          proportion to their observed traversal ``levels`` — under a
          lockstep (ring-overlap) schedule the block wall is shared, so
          depth share is the per-replica signal — feeding a per-replica
          EWMA of per-round seconds;
        * commits happen at *drain* time and the accumulate is masked by
          the commit outcome (donation-safe double-dispatch);
        * between blocks the policy moves pending rounds: ``steal`` pulls
          into idle lanes and duplicates the straggler's round at the
          tail, ``redeal`` re-packs every pending round when the EWMA
          ratio crosses ``straggler_factor``.
        """
        from repro.distributed.fault_tolerance import ReplicaLostError

        fr = self.fr
        s = self.schedule.batch_size
        k = self.schedule.derived_per_round
        rounds = self.schedule.rounds
        queues = split_rounds(
            len(rounds), fr, self._committed_union(), round_costs=self.round_costs
        )

        prior = self.prior_round_s
        ewma: list[float | None] = [None] * fr
        observed = [False] * fr

        def est(r: int) -> float:
            if ewma[r] is not None:
                return ewma[r]
            return prior if prior is not None else 1.0

        bc_acc = None
        ns_by_root: dict[int, float] = dict(self._ns0)
        rounds_run = 0
        fwd_cols = 0
        bwd_cols = 0
        stopped_early = False
        blocks_since_snapshot = 0
        block = 0
        block_times: list[float] = []
        stats = {
            "policy": self.straggler,
            "factor": self.straggler_factor,
            "replicas": fr,
            "rounds_stolen": 0,
            "rounds_redealt": 0,
            "redeal_events": 0,
            "duplicates_dispatched": 0,
            "duplicates_discarded": 0,
            "per_replica_wall_s": [0.0] * fr,
            "per_replica_rounds": [0] * fr,
            "per_replica_levels": [0] * fr,
            "idle_levels": 0,
            "idle_s_est": 0.0,
        }
        was_flagged = False

        def flagged() -> bool:
            vals = [
                ewma[r] for r in range(fr)
                if observed[r] and r not in self._dead_lanes
            ]
            if len(vals) < 2:
                return False
            lo, hi = min(vals), max(vals)
            return lo > 0.0 and hi > self.straggler_factor * lo

        def on_replica_loss(err, lane_rids, duplicate):
            """Self-heal a lost replica lane (nothing from the failed
            dispatch landed): consult the elasticity planner, move the
            dead lane's ledger commits to a survivor (the committed
            union — exactly-once — is unchanged), re-deal its backlog,
            and continue at reduced effective fr (the dead lane is dealt
            only padding from here on, so shapes stay static)."""
            from repro.distributed.fault_tolerance import plan_elastic_remesh

            dead = int(getattr(err, "replica", -1))
            if dead < 0 or dead >= fr or dead in self._dead_lanes:
                raise err
            self._dead_lanes.add(dead)
            survivors = [r for r in range(fr) if r not in self._dead_lanes]
            if not survivors:
                raise err
            self.recovery["remesh_events"] += 1
            self.recovery["dead_replicas"] = sorted(self._dead_lanes)
            # the failed block's owned rounds go back to the front of a
            # surviving queue (duplicates' owners requeue their own copy)
            for r in range(fr):
                rid = lane_rids[r]
                if rid is None or duplicate[r]:
                    continue
                if any(led.is_committed(rid) for led in self.ledgers):
                    continue
                target = r if r in survivors else survivors[0]
                queues[target].insert(0, rid)
            taken = self.ledgers[survivors[0]].merge(self.ledgers[dead])
            orphans = list(queues[dead])
            queues[dead] = []
            for i, rid in enumerate(orphans):
                queues[survivors[i % len(survivors)]].append(rid)
            sub, _ = redeal_rounds(
                [queues[r] for r in survivors], [est(r) for r in survivors]
            )
            for r, q in zip(survivors, sub):
                queues[r] = q
            try:
                total = 1
                for dim in self.mesh_shape:
                    total *= dim
                pod_ax = (
                    self.mesh_axes.index("pod") if "pod" in self.mesh_axes else 0
                )
                per_pod = max(1, total // max(1, self.mesh_shape[pod_ax]))
                plan = plan_elastic_remesh(
                    self.mesh_shape, self.mesh_axes,
                    per_pod * len(self._dead_lanes),
                )
                logger.warning(
                    "replica %d lost: re-mesh %s -> %s (%s); merged %d "
                    "committed rounds into replica %d, re-dealt %d pending",
                    dead, self.mesh_shape, plan.shape, plan.note, taken,
                    survivors[0], len(orphans),
                )
            except Exception as pe:  # planning is advisory, never fatal
                logger.warning(
                    "replica %d lost: elastic re-mesh planning failed "
                    "(%s: %s); continuing on %d surviving lanes",
                    dead, type(pe).__name__, pe, len(survivors),
                )

        def snapshot():
            with span("bc.driver.checkpoint"):
                self.checkpoint.save(
                    self._collect_bc(bc_acc),
                    ns_by_root,
                    [led.state() for led in self.ledgers],
                    self._fingerprint,
                    stats=self._stats_state(),
                )

        while any(queues):
            alive = [r for r in range(fr) if r not in self._dead_lanes]
            # ---------------------------------------- policy: move work
            if self.straggler == "redeal":
                lengths = [len(queues[r]) for r in alive]
                fire = flagged()
                tail_gap = min(lengths) == 0 and max(lengths) >= 2
                if (fire and not was_flagged) or tail_gap:
                    sub, moved = redeal_rounds(
                        [queues[r] for r in alive], [est(r) for r in alive]
                    )
                    for r, q in zip(alive, sub):
                        queues[r] = q
                    if moved:
                        stats["rounds_redealt"] += moved
                        stats["redeal_events"] += 1
                        logger.info(
                            "straggler redeal: moved %d pending rounds "
                            "(EWMA s/round: %s)",
                            moved,
                            [None if ewma[r] is None else round(ewma[r], 6)
                             for r in alive],
                        )
                was_flagged = fire

            # ----------------------------------------------- form block
            lane_rids: list[int | None] = [
                queues[r].pop(0)
                if r not in self._dead_lanes and queues[r]
                else None
                for r in range(fr)
            ]
            duplicate = [False] * fr
            if self.straggler == "steal":
                # idle lanes pull from the heaviest remaining backlog
                for r in sorted(alive, key=est):
                    if lane_rids[r] is not None:
                        continue
                    donors = [d for d in alive if queues[d]]
                    if not donors:
                        continue
                    donor = max(donors, key=lambda d: len(queues[d]) * est(d))
                    lane_rids[r] = queues[donor].pop(0)
                    stats["rounds_stolen"] += 1
                # tail: still-idle lanes back up the presumed straggler's
                # round instead of dispatching padding (first commit wins)
                working = [r for r in alive if lane_rids[r] is not None]
                idle = [r for r in alive if lane_rids[r] is None]
                if working and idle:
                    slowest = max(working, key=est)
                    for r in idle:
                        lane_rids[r] = lane_rids[slowest]
                        duplicate[r] = True
                        stats["duplicates_dispatched"] += 1
            if all(rid is None for rid in lane_rids):
                continue

            srcs = np.full((fr, s), -1, np.int32)
            ders = np.full((fr, k, 3), -1, np.int32)
            for r, rid in enumerate(lane_rids):
                if rid is not None:
                    srcs[r] = rounds[rid].sources
                    ders[r] = rounds[rid].derived

            # ------------------------------------- dispatch + observe
            # every dispatch its own number: a lost one leaves a block
            # span with no drain and no entry in block_times
            block += 1
            with span("bc.driver.block", block=block) as blk:
                try:
                    with span("bc.driver.dispatch", block=block):
                        out = self._dispatch_block(srcs, ders)
                except ReplicaLostError as e:
                    if int(getattr(e, "replica", -1)) < 0:
                        # unattributed loss (the watchdog escalated a wedged
                        # dispatch without knowing *which* lane hung): suspect
                        # the slowest live lane by EWMA — the one most likely
                        # to be the straggling/wedged participant
                        cands = [
                            r for r in alive if lane_rids[r] is not None
                        ] or alive
                        suspect = max(cands, key=est)
                        e = ReplicaLostError(
                            suspect,
                            f"{e}; suspecting replica {suspect} "
                            f"(slowest EWMA among the dispatched lanes)",
                        )
                    on_replica_loss(e, lane_rids, duplicate)
                    continue
                bc_blk, ns_dev, roots_dev, levels_dev, _integ = out
                if levels_dev is None:
                    raise ValueError(
                        "straggler scheduling needs a round_fn returning "
                        "(bc, ns, roots, levels); got a legacy 3-tuple"
                    )
                with span("bc.driver.drain", block=block):
                    jax.block_until_ready(bc_blk)
                wall = blk.elapsed()
                if bc_blk.shape[0] != fr:
                    raise ValueError(
                        f"straggler scheduling needs a per-replica bc block "
                        f"(leading dim {fr}); got shape {tuple(bc_blk.shape)}"
                    )
                levels_np = np.asarray(levels_dev).reshape(-1).astype(np.int64)
                # duplicate lanes ran work they will discard: they get no wall
                # attribution and no EWMA update (their "cost" belongs to the
                # round's owner lane, which is also in this block)
                own = [
                    r for r in range(fr)
                    if lane_rids[r] is not None and not duplicate[r]
                ]
                lv_total = int(levels_np[own].sum())
                lv_max = int(levels_np[own].max()) if own else 0
                for r in own:
                    share = (
                        levels_np[r] / lv_total if lv_total > 0 else 1.0 / len(own)
                    )
                    obs = wall * float(share)
                    ewma[r] = (
                        obs
                        if ewma[r] is None and prior is None
                        else _EWMA_ALPHA * obs
                        + (1.0 - _EWMA_ALPHA) * (ewma[r] if ewma[r] is not None else prior)
                    )
                    observed[r] = True
                    stats["per_replica_wall_s"][r] += obs
                    stats["per_replica_levels"][r] += int(levels_np[r])
                    stats["idle_levels"] += lv_max - int(levels_np[r])
                if lv_max > 0 and own:
                    idle_frac = sum(lv_max - int(levels_np[r]) for r in own) / (
                        len(own) * lv_max
                    )
                    stats["idle_s_est"] += wall * idle_frac

                # ---------------------- duplicate vote (free DMR, steal tail)
                # a speculatively duplicated round ran the identical
                # deterministic computation on two replica lanes — compare
                # their bc digests; a mismatch means one lane produced
                # silently corrupt data, so neither copy can be trusted:
                # quarantine the round (no commit, both lanes masked to zero)
                # and re-dispatch it to its owner as the tie-breaker vote.
                quarantined_rids: set[int] = set()
                lane_sums = None
                if self.integrity != "off" and (
                    any(duplicate) or self._pending_votes
                ):
                    lane_sums = np.asarray(
                        jax.device_get(self._block_digest(bc_blk)[0]), np.float64
                    ).reshape(-1)
                if lane_sums is not None and any(duplicate):
                    ist = self.recovery["integrity"]
                    for r in range(fr):
                        if not duplicate[r]:
                            continue
                        rid = lane_rids[r]
                        owner = next(
                            o for o in range(fr)
                            if lane_rids[o] == rid and not duplicate[o]
                        )
                        ist["votes"] += 1
                        vscale = max(
                            1.0, abs(lane_sums[owner]), abs(lane_sums[r])
                        )
                        if (
                            abs(lane_sums[r] - lane_sums[owner])
                            > VOTE_RTOL * vscale
                        ):
                            ist["vote_mismatches"] += 1
                            if rid in quarantined_rids:
                                continue  # already requeued by another copy
                            ist["quarantined_rounds"] += 1
                            quarantined_rids.add(rid)
                            self._pending_votes[rid] = {
                                "owner": float(lane_sums[owner]),
                                "duplicate": float(lane_sums[r]),
                            }
                            queues[owner].insert(0, rid)
                            logger.warning(
                                "duplicate-vote mismatch on round %d "
                                "(owner lane %d sum %.6g vs duplicate lane %d "
                                "sum %.6g); round quarantined, re-dispatching "
                                "as tie-breaker",
                                rid, owner, lane_sums[owner], r, lane_sums[r],
                            )

                # -------------------------- drain: commit-or-discard + add
                # originals commit before their speculative duplicates, so a
                # backup copy never out-commits the lane that owns the round
                # (keeps duplicates_discarded and per-replica attribution
                # honest; exactly-once holds in either order)
                mask = np.zeros(fr, np.float32)
                roots_np = np.asarray(roots_dev)
                ns_np = np.asarray(ns_dev, np.float64)
                for r in sorted(range(fr), key=lambda r: duplicate[r]):
                    rid = lane_rids[r]
                    if rid is None or rid in quarantined_rids:
                        continue
                    if self._try_commit(r, rid):
                        mask[r] = 1.0
                        rounds_run += 1
                        stats["per_replica_rounds"][r] += 1
                        fwd_cols += int((srcs[r] >= 0).sum())
                        bwd_cols += int(
                            (srcs[r] >= 0).sum() + (ders[r, :, 0] >= 0).sum()
                        )
                        for root, nv in zip(roots_np[r], ns_np[r]):
                            if root >= 0:
                                ns_by_root[int(root)] = float(nv)
                        pend = self._pending_votes.pop(rid, None)
                        if pend is not None and lane_sums is not None:
                            # tie-breaker verdict: which original lane agreed
                            # with this clean recompute (i.e. was correct)
                            tie = float(lane_sums[r])

                            def close(a, b):
                                return abs(a - b) <= VOTE_RTOL * max(
                                    1.0, abs(a), abs(b)
                                )

                            matched = (
                                "owner" if close(tie, pend["owner"])
                                else "duplicate" if close(tie, pend["duplicate"])
                                else "neither"
                            )
                            self.recovery["integrity"]["vote_verdicts"].append(
                                {"round": int(rid), "matched": matched}
                            )
                            logger.warning(
                                "duplicate-vote tie-breaker for round %d: "
                                "the %s lane was correct", rid, matched,
                            )
                    elif duplicate[r]:
                        stats["duplicates_discarded"] += 1
                mask_dev = jnp.asarray(mask)
                bc_acc = (
                    self._masked_scale(bc_blk, mask_dev)
                    if bc_acc is None
                    else self._masked_accumulate(bc_acc, bc_blk, mask_dev)
                )

                blocks_since_snapshot += 1
                if self.checkpoint is not None and (
                    blocks_since_snapshot >= self.checkpoint_every
                ):
                    snapshot()
                    blocks_since_snapshot = 0
                # the stop seam: commits already happened at this block's
                # drain (exactly-once is settled), so halting here leaves a
                # clean committed prefix for the checkpoint/re-deal to own
                if self.stop_rule is not None:
                    bc_now = self._collect_bc(bc_acc)
                    with span("bc.driver.stop_rule", block=block):
                        stopped_early = bool(self.stop_rule(bc_now, len(block_times) + 1))
            block_times.append(blk.seconds)
            if stopped_early:
                logger.info(
                    "stop rule fired after %d dispatch blocks "
                    "(%d rounds committed); halting dispatch",
                    len(block_times), rounds_run,
                )
                break

        if self.checkpoint is not None:
            snapshot()
        logger.info(
            "straggler=%s: %d rounds, %d stolen, %d re-dealt (%d events), "
            "%d/%d duplicates discarded, idle ≈ %.3fs of %.3fs in blocks",
            self.straggler,
            rounds_run,
            stats["rounds_stolen"],
            stats["rounds_redealt"],
            stats["redeal_events"],
            stats["duplicates_discarded"],
            stats["duplicates_dispatched"],
            stats["idle_s_est"],
            sum(block_times),
        )
        return BCResult(
            bc=self._finalize(bc_acc, ns_by_root),
            schedule=self.schedule,
            rounds_run=rounds_run,
            forward_columns=fwd_cols,
            backward_columns=bwd_cols,
            block_times=block_times,
            stopped_early=stopped_early,
            stop_stats=getattr(self.stop_rule, "stats", None),
            roots_accumulated=self._count_roots(
                sorted(self._committed_union())
            ),
            straggler_stats=stats,
            recovery_stats=dict(self.recovery),
        )
