"""Source-round scheduler.

Brandes' outer loop is embarrassingly parallel over source vertices; the
scheduler turns the eligible source set into fixed-shape *rounds* (the
unit of jit compilation, checkpointing, straggler re-execution and
sub-cluster distribution):

* every round holds ``batch_size`` explicit sources (padded with -1) and
  up to ``derived_per_round`` 2-degree derived columns (c, a_pos, b_pos),
  a width sized from the claims: a schedule that claims no 2-degree
  vertex carries none, so its backward state is ``batch_size`` wide;
* a derived vertex's two neighbors must be explicit sources *of the same
  round* (their forward columns feed Alg. 7); the packer keeps triples
  intact and demotes a triple to an explicit source on conflict —
  demotion is always correct, only marginally slower;
* rounds are the elastic work unit: on a shrink/grow event the remaining
  rounds are simply re-dealt to the surviving sub-clusters
  (distributed/fault_tolerance.py), and a straggling round can be
  re-issued wholesale because BC accumulation is additive and
  order-independent.

:func:`split_rounds` and :func:`redeal_rounds` are the sub-cluster side
of that elasticity: the static per-replica deal and the straggler
re-deal re-pack consumed by :class:`repro.core.driver.BCDriver`.  Both
are pure functions over round ids so the scheduling policy is
unit-testable without a mesh.
"""
from __future__ import annotations

import dataclasses
import logging

import numpy as np

from repro.core.heuristics.one_degree import OneDegreeReduction, one_degree_reduce
from repro.core.heuristics.two_degree import claim_two_degree
from repro.graphs.graph import Graph

logger = logging.getLogger(__name__)

__all__ = [
    "Round",
    "Schedule",
    "build_schedule",
    "HEURISTICS_MODES",
    "ROOT_ORDERS",
    "MXU_LANES",
    "bfs_depths",
    "estimate_eccentricities",
    "split_rounds",
    "redeal_rounds",
    "validate_batch_size",
]

#: The heuristics selector (paper Fig. 12 naming), the single source of
#: truth for ``--heuristics`` choices and the documentation drift check
#: (tools/check_docs.py): "h0" no heuristics | "h1" 1-degree reduction |
#: "h2" 2-degree DMF | "h3" both; the "t" suffix ("h1t" / "h3t") runs the
#: 1-degree pass to a fixed point (beyond-paper pendant-tree contraction,
#: heuristics/one_degree.py).
HEURISTICS_MODES = ("h0", "h1", "h2", "h3", "h1t", "h3t")

#: explicit-source round-packing orders: "id" fills rounds in vertex-id
#: order (legacy); "eccentricity" sorts by sampled eccentricity
#: descending so similar-depth roots share a round — a round's traversal
#: runs to its *deepest* root's level, so a shallow root batched with a
#: deep one burns the depth difference as masked no-op levels, and under
#: replica lockstep (ring overlap) a whole replica can idle the same way
ROOT_ORDERS = ("id", "eccentricity")

#: MXU lane width: the [n, s] frontier matmul pads the source dimension
#: to this; the batch_size validator hints when the padding wastes more
#: than half a tile
MXU_LANES = 128


def validate_batch_size(
    batch_size: int, *, lanes: int = MXU_LANES, population: int | None = None
) -> int:
    """Validate the multi-source batch width (both entrypoints funnel
    through :func:`build_schedule`, so this covers them all).

    Rejects ``< 1`` outright; logs a hint when the padded column width
    wastes more than half an MXU tile (e.g. ``batch_size=48`` pads to
    128 and masks 80 dead lanes every matmul).  ``population`` is the
    root-pool size actually being scheduled (e.g. a sampled run's
    ``sample_k``): when it is the binding constraint — no wider batch
    could ever fill — the hint is suppressed rather than nagging the
    user to raise a number that cannot help.
    """
    batch_size = int(batch_size)
    if batch_size < 1:
        raise ValueError(
            f"batch_size must be >= 1, got {batch_size}: every round needs "
            "at least one explicit source column"
        )
    pad = (-batch_size) % lanes
    if pad > lanes // 2 and (population is None or population > batch_size):
        better = batch_size - (batch_size % lanes) or lanes
        logger.warning(
            "batch_size=%d pads the source dimension to %d (%d wasted MXU "
            "lanes, more than half a %d-lane tile); %d or a multiple of %d "
            "wastes none",
            batch_size, batch_size + pad, pad, lanes, better, lanes,
        )
    return batch_size


def bfs_depths(graph: Graph, root: int) -> np.ndarray:
    """Exact BFS depth of every vertex from ``root`` (-1 = unreached).

    Vectorized over the symmetric arc list (no per-vertex Python loop):
    each step scatters the frontier through ``src -> dst`` masks.
    """
    depth = np.full(graph.n, -1, np.int64)
    depth[root] = 0
    frontier = np.zeros(graph.n, bool)
    frontier[root] = True
    d = 0
    while frontier.any():
        nxt = np.zeros(graph.n, bool)
        nxt[graph.dst[frontier[graph.src]]] = True
        nxt &= depth < 0
        if not nxt.any():
            break
        d += 1
        depth[nxt] = d
        frontier = nxt
    return depth


def estimate_eccentricities(
    graph: Graph, num_samples: int = 8, seed: int = 0
) -> np.ndarray:
    """Sampled lower-bound eccentricity per vertex (farthest-first BFS).

    Landmarks are chosen farthest-first: the first at random, each next
    at the vertex maximizing its distance to all previous landmarks —
    with unreached vertices (other components) counting as infinitely
    far, so every connected component receives at least one landmark
    *before* the ``num_samples`` refinement budget applies (coverage is
    what makes the estimate usable as a round-packing key on disjoint
    unions; a component with no landmark would estimate 0 and sort with
    the shallow cliques).  ``ecc[v]`` is the max over landmarks of
    ``dist(v, landmark)`` — a lower bound on the true eccentricity,
    exact at ≥1 landmark per component endpoints and, for packing, only
    the *relative* order matters.
    """
    if graph.n == 0:
        return np.zeros(0, np.int64)
    rng = np.random.default_rng(seed)
    ecc = np.zeros(graph.n, np.int64)
    far = np.iinfo(np.int64).max
    mind = np.full(graph.n, far, np.int64)  # min distance to any landmark
    root = int(rng.integers(graph.n))
    taken = 0
    while True:
        depth = bfs_depths(graph, root)
        reached = depth >= 0
        np.maximum(ecc, depth, where=reached, out=ecc)
        # the landmark's own eccentricity is exact from its BFS (it would
        # otherwise self-measure 0 and sort below every shallow root)
        ecc[root] = max(ecc[root], int(depth[reached].max()))
        np.minimum(mind, depth, where=reached, out=mind)
        taken += 1
        root = int(np.argmax(mind))
        if mind[root] == far:
            continue  # an uncovered component: keep going past the budget
        if taken >= num_samples or mind[root] == 0:
            return ecc


@dataclasses.dataclass(frozen=True)
class Round:
    sources: np.ndarray  # int32 [batch_size]; -1 = padding
    derived: np.ndarray  # int32 [derived_per_round, 3]; rows (c, a_pos, b_pos); -1 pad


@dataclasses.dataclass(frozen=True)
class Schedule:
    rounds: list[Round]
    batch_size: int
    derived_per_round: int
    num_explicit: int
    num_derived: int
    num_leaf_skipped: int  # 1-degree vertices never traversed
    num_isolated_omega: int  # residual-isolated vertices resolved analytically
    analytic_corrections: np.ndarray  # f64 [k, 2] rows (v, n_comp) resolved w/o traversal
    #: per-round expected traversal depth (max sampled eccentricity over
    #: the round's roots) — the cost prior for the replica deal
    #: (:func:`split_rounds` ``round_costs``); None unless the schedule
    #: was built with ``root_order="eccentricity"``
    round_depths: np.ndarray | None = None


def _finish_round(src_list, derived_list, batch_size, derived_per_round) -> Round:
    sources = np.full(batch_size, -1, dtype=np.int32)
    sources[: len(src_list)] = src_list
    derived = np.full((derived_per_round, 3), -1, dtype=np.int32)
    for k, (c, ap, bp) in enumerate(derived_list):
        derived[k] = (c, ap, bp)
    return Round(sources=sources, derived=derived)


def build_schedule(
    graph: Graph,
    batch_size: int = 32,
    heuristics: str = "h0",
    derived_per_round: int | None = None,
    root_order: str = "id",
    ecc_samples: int = 8,
    ecc_seed: int = 0,
    roots: np.ndarray | None = None,
) -> tuple[Schedule, OneDegreeReduction | None, Graph, np.ndarray]:
    """Plan the full BC computation.

    Args:
      graph:      input undirected graph.
      batch_size: explicit sources per round (the multi-source width; the
                  paper's sub-cluster work unit).
      heuristics: one of :data:`HEURISTICS_MODES` — "h0" none |
                  "h1" 1-degree | "h2" 2-degree | "h3" both; "h1t"/"h3t"
                  contract whole pendant trees (beyond-paper exhaustive
                  1-degree pass).
      derived_per_round: cap on derived columns per round (default:
                  ``min(batch_size // 2, claimed triples)`` — a triple
                  contributes ≥2 sources, and a schedule with no claim,
                  as under "h0"/"h1", derives nothing: k = 0).
      root_order: one of :data:`ROOT_ORDERS` — "id" (legacy vertex-id
                  fill) or "eccentricity" (sampled-eccentricity
                  descending, packing similar-depth roots into the same
                  round; also populates ``Schedule.round_depths`` so the
                  replica deal can balance expected cost).
      ecc_samples / ecc_seed: :func:`estimate_eccentricities` budget and
                  landmark seed (only read under "eccentricity").
      roots:      optional explicit root subset (vertex ids): only
                  eligible sources in this set are scheduled — the
                  source-sampling seam (repro.serving).  Requires
                  ``heuristics="h0"``: the 1-/2-degree analytic credits
                  are not separable per root, so a sampled subset could
                  not be rescaled into an unbiased estimate.  Root
                  ordering (including eccentricity packing) applies to
                  the subset unchanged.

    Returns (schedule, one_degree_result_or_None, residual_graph, omega).
    """
    if heuristics not in HEURISTICS_MODES:
        raise ValueError(
            f"unknown heuristics mode {heuristics!r}; expected one of "
            f"{HEURISTICS_MODES}"
        )
    if root_order not in ROOT_ORDERS:
        raise ValueError(
            f"unknown root_order {root_order!r}; expected one of {ROOT_ORDERS}"
        )
    batch_size = validate_batch_size(
        batch_size, population=None if roots is None else len(roots)
    )
    if roots is not None and heuristics != "h0":
        raise ValueError(
            "a root subset (source sampling) requires heuristics='h0': "
            "the 1-/2-degree analytic corrections are not per-root "
            f"additive, so a sampled schedule under {heuristics!r} could "
            "not be rescaled into an unbiased estimator"
        )
    use_h1 = heuristics in ("h1", "h3", "h1t", "h3t")
    use_h2 = heuristics in ("h2", "h3", "h3t")
    exhaustive = heuristics.endswith("t")  # beyond-paper tree contraction

    prep = one_degree_reduce(graph, exhaustive=exhaustive) if use_h1 else None
    residual = prep.residual if prep is not None else graph
    omega = prep.omega if prep is not None else np.zeros(graph.n, dtype=np.float64)

    res_deg = residual.degrees()
    eligible = res_deg >= 1  # traversal-worthy sources
    if roots is not None:
        root_ids = np.asarray(roots, np.int64)
        if root_ids.size and (
            root_ids.min() < 0 or root_ids.max() >= graph.n
        ):
            raise ValueError(
                f"root subset contains out-of-range vertex ids "
                f"(n = {graph.n})"
            )
        keep = np.zeros(graph.n, bool)
        keep[root_ids] = True
        eligible &= keep
    num_leaf_skipped = int(prep.num_removed) if prep is not None else 0

    # residual-isolated vertices with removed leaves: analytic component
    # size n = 1 + omega (star centers, K2 leaves) — no round needed.
    removed_mask = prep.removed if prep is not None else np.zeros(graph.n, bool)
    iso_omega = np.nonzero((res_deg == 0) & (omega > 0) & ~removed_mask)[0]
    analytic = np.stack(
        [iso_omega, 1 + omega[iso_omega]], axis=1
    ).astype(np.float64) if iso_omega.size else np.zeros((0, 2), np.float64)

    triples: list[tuple[int, int, int]] = []
    if use_h2:
        adj = residual.adjacency_lists()
        triples = claim_two_degree(res_deg, adj, eligible)
    derived_set = {c for c, _, _ in triples}
    if derived_per_round is None:
        derived_per_round = min(batch_size // 2, len(triples))

    rounds: list[Round] = []
    cur_src: list[int] = []
    cur_pos: dict[int, int] = {}
    cur_der: list[tuple[int, int, int]] = []
    consumed: set[int] = set()
    demoted: list[int] = []

    def flush():
        nonlocal cur_src, cur_pos, cur_der
        if cur_src or cur_der:
            rounds.append(_finish_round(cur_src, cur_der, batch_size, derived_per_round))
        cur_src, cur_pos, cur_der = [], {}, []

    # 1) place triples (sorted so shared-neighbor triples cluster)
    for c, a, b in sorted(triples, key=lambda t: (t[1], t[2])):
        if batch_size < 2 or derived_per_round < 1:
            demoted.append(c)  # a triple needs two co-resident sources and a slot
            continue
        if a in consumed and a not in cur_pos or b in consumed and b not in cur_pos:
            demoted.append(c)  # neighbor already ran in a closed round
            continue
        need = [v for v in (a, b) if v not in cur_pos]
        if len(cur_src) + len(need) > batch_size or len(cur_der) >= derived_per_round:
            flush()
            need = [v for v in (a, b) if v not in cur_pos]
            if a in consumed or b in consumed:
                demoted.append(c)
                continue
        for v in need:
            cur_pos[v] = len(cur_src)
            cur_src.append(v)
            consumed.add(v)
        cur_der.append((c, cur_pos[a], cur_pos[b]))

    # 2) fill with the remaining explicit sources — in vertex-id order,
    # or deepest-first under "eccentricity" so each round packs
    # similar-depth roots (the round runs to its deepest root's level)
    ecc = (
        estimate_eccentricities(residual, num_samples=ecc_samples, seed=ecc_seed)
        if root_order == "eccentricity"
        else None
    )
    explicit_rest = [
        int(v)
        for v in np.nonzero(eligible)[0]
        if v not in consumed and v not in derived_set
    ] + demoted
    if ecc is not None:
        explicit_rest.sort(key=lambda v: (-int(ecc[v]), v))
    for v in explicit_rest:
        if len(cur_src) >= batch_size:
            flush()
        cur_pos[v] = len(cur_src)
        cur_src.append(v)
        consumed.add(v)
    flush()

    num_derived = sum(int((r.derived[:, 0] >= 0).sum()) for r in rounds)
    num_explicit = sum(int((r.sources >= 0).sum()) for r in rounds)
    round_depths = None
    if ecc is not None:
        round_depths = np.array(
            [
                max(
                    (
                        int(ecc[v])
                        for v in np.concatenate((r.sources, r.derived[:, 0]))
                        if v >= 0
                    ),
                    default=0,
                )
                for r in rounds
            ],
            np.int64,
        )
    schedule = Schedule(
        rounds=rounds,
        batch_size=batch_size,
        derived_per_round=derived_per_round,
        num_explicit=num_explicit,
        num_derived=num_derived,
        num_leaf_skipped=num_leaf_skipped,
        num_isolated_omega=int(iso_omega.size),
        analytic_corrections=analytic,
        round_depths=round_depths,
    )
    return schedule, prep, residual, omega


def split_rounds(
    num_rounds: int, fr: int, committed=(), round_costs=None
) -> list[list[int]]:
    """Static per-replica deal of a schedule's round ids.

    Replica ``r`` receives rounds ``r, r+fr, r+2fr, …`` — the interleaved
    deal, chosen because it reproduces exactly the lane assignment of the
    legacy single-ledger block loop (block ``i`` = rounds
    ``[i·fr, (i+1)·fr)``), so ``straggler="none"`` and the multi-ledger
    policies start from the *same* static assignment and any wall-time
    difference is attributable to the re-deal alone.  Rounds in
    ``committed`` (e.g. from a resumed checkpoint) are excluded.

    ``round_costs`` (one expected cost per round, e.g.
    ``Schedule.round_depths`` from an eccentricity-ordered schedule)
    switches to the *cost-packed* deal: the pool is sorted costliest
    first and consecutive ``fr``-tuples dealt one per lane — the same
    shape as the straggler's :func:`redeal_rounds`, but seeded from the
    eccentricity prior instead of waiting for the EWMA to learn it.  A
    dispatch block then co-schedules similar-cost rounds, so under
    replica lockstep no lane burns masked no-op levels waiting on a much
    deeper partner, and total expected cost balances across ledgers.
    """
    if fr < 1:
        raise ValueError(f"need at least one replica, got fr={fr}")
    done = set(committed)
    if round_costs is None:
        return [
            [rid for rid in range(r, num_rounds, fr) if rid not in done]
            for r in range(fr)
        ]
    costs = [float(c) for c in round_costs]
    if len(costs) != num_rounds:
        raise ValueError(
            f"{num_rounds} rounds but {len(costs)} round costs"
        )
    pool = sorted(
        (rid for rid in range(num_rounds) if rid not in done),
        key=lambda rid: (-costs[rid], rid),
    )
    queues: list[list[int]] = [[] for _ in range(fr)]
    for i, rid in enumerate(pool):
        queues[i % fr].append(rid)
    return queues


def redeal_rounds(
    queues: list[list[int]], lane_cost: list[float]
) -> tuple[list[list[int]], int]:
    """Re-deal pending rounds across replica queues (straggler recovery).

    A sub-cluster dispatch block co-schedules one round per replica and —
    under a ring overlap policy, where the replica axis joins the
    loop-bound reductions — costs the *max* over its rounds' traversal
    depths: a deep round paired with a shallow one makes the shallow
    replica burn the depth difference as masked no-op levels.  The
    re-deal therefore packs *similar-cost* rounds into the same block:
    every pending round is estimated at its current owner's per-round
    cost (the driver's EWMA — rounds were dealt to that lane, so the
    lane's observed history is the best available prior for them), the
    pool is sorted costliest-first, and consecutive ``fr``-tuples are
    dealt one per lane.  The straggler's backlog thus drains into the
    fastest replica's queue head while cheap rounds pair with cheap.

    Returns ``(new_queues, moved)`` where ``moved`` counts rounds that
    changed lanes.  Pure function — order inside a lane is deterministic
    (cost desc, round id asc) so a re-deal is reproducible across a
    kill-and-resume.
    """
    fr = len(queues)
    if fr != len(lane_cost):
        raise ValueError(f"{fr} queues but {len(lane_cost)} lane costs")
    owner = {rid: r for r, q in enumerate(queues) for rid in q}
    pool = sorted(owner, key=lambda rid: (-lane_cost[owner[rid]], rid))
    new_queues: list[list[int]] = [[] for _ in range(fr)]
    for i, rid in enumerate(pool):
        new_queues[i % fr].append(rid)
    moved = sum(
        1 for r, q in enumerate(new_queues) for rid in q if owner[rid] != r
    )
    return new_queues, moved
