"""Single-device betweenness centrality entry point (MGBC without the mesh).

Composes the round scheduler, the operator layer and the shared driver
(:mod:`repro.core.driver`) into the full exact-BC computation.  The
distributed version (:mod:`repro.core.distributed`) is the same
driver/round body over the 2-D-partitioned operators; this module is
both the small-graph production path and the semantic reference for it.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.driver import (
    BCDriver,
    BCResult,
    apply_reduction_corrections,
    normalize_straggler,
    traversal_round,
)
from repro.core.operators import (
    PallasDenseOperator,
    WeightedDenseOperator,
    WeightedSparseOperator,
    auto_delta,
    normalize_overlap,
)
from repro.core.scheduler import build_schedule
from repro.graphs.graph import Graph

# heuristics usable under weighted traversal: the 1-degree reduction (and
# its tree-contraction variant) is purely combinatorial — every path
# to/through a pendant subtree crosses its anchor whatever the edge
# weights — but the 2-degree derivation (h2/h3/h3t) rewrites *levels*
# (lvl_c = min(lvl_a, lvl_b) + 1), which assumes unit edge lengths.
WEIGHTED_HEURISTICS = ("h0", "h1", "h1t")

__all__ = [
    "BCResult",
    "betweenness_centrality",
    "make_round_fn",
    "apply_reduction_corrections",
    "apply_sampling_rescale",
    "ENGINE_KINDS",
]

# the single source of truth for --engine choices (launch/bc.py, benchmarks)
ENGINE_KINDS = ("dense", "sparse", "pallas", "pallas_bf16")


def make_round_fn(
    operator_fn,
    n: int,
    num_levels: int | None = None,
):
    """Build the jit-able per-round function.

    Args:
      operator_fn:     closure () -> TraversalOperator (captures adjacency).
      n:               vertex count (kept for signature stability).
      num_levels:      static level bound (dry-run) or None (early exit).

    The returned function maps
      (sources i32 [s], derived i32 [k, 3], omega f32 [n])
        -> (bc_round f32 [n], ns f32 [s+k], roots i32 [s+k], levels i32 [])
    """
    del n  # the operator knows its own row count

    def round_fn(sources, derived, omega):
        return traversal_round(
            operator_fn(), sources, derived, omega, num_levels=num_levels
        )

    return round_fn


def _make_operator_fn(graph_residual, n, engine_kind):
    """Graph operands + operator factory for an engine kind.

    Returns ``(operands, make_op)``: host-built device arrays and a
    function ``make_op(*operands) -> TraversalOperator``.  The round
    function takes the operands as jit arguments — a closed-over array
    would be embedded in the program as a constant (gigabytes of HLO at
    chip scale).
    """
    if engine_kind == "dense":
        adjacency = jnp.asarray(graph_residual.dense_adjacency(np.float32))
        return (adjacency,), engine.make_dense_operator
    if engine_kind == "sparse":
        src_p, dst_p, _ = graph_residual.padded_arcs(multiple=8)
        return (
            (jnp.asarray(src_p), jnp.asarray(dst_p)),
            lambda src, dst: engine.make_sparse_operator(src, dst, n),
        )
    if engine_kind in ("pallas", "pallas_bf16"):
        # 0/1 entries are exact in bf16: build the host copy in the
        # engine's dtype so no f32 duplicate is shipped or converted
        dt = np.float32 if engine_kind == "pallas" else jnp.bfloat16
        return (jnp.asarray(graph_residual.dense_adjacency(dt)),), PallasDenseOperator
    raise ValueError(f"unknown engine {engine_kind!r}")


def _make_weighted_operator_fn(graph_residual, n, engine_kind, delta):
    """Weighted operands + operator factory (bucketed traversal, all
    engine kinds; same ``(operands, make_op)`` contract as
    :func:`_make_operator_fn`).

    "sparse" keeps the arc-list layout; "dense"/"pallas"/"pallas_bf16"
    share the dense float32 weight-matrix operator — the weighted bucket
    steps are XLA contractions (no fused Pallas bucket kernels yet; see
    operators.py), and weights stay float32 even under pallas_bf16
    because distances feed exact equality masks.
    """
    if engine_kind == "sparse":
        src_p, dst_p, _ = graph_residual.padded_arcs(multiple=8)
        w_p = graph_residual.padded_arc_weights(multiple=8)
        return (
            (jnp.asarray(src_p), jnp.asarray(dst_p), jnp.asarray(w_p)),
            lambda src, dst, w: WeightedSparseOperator(src, dst, w, n, delta),
        )
    if engine_kind in ("dense", "pallas", "pallas_bf16"):
        weights = jnp.asarray(graph_residual.dense_weights(np.float32))
        return (weights,), lambda w: WeightedDenseOperator(w, delta)
    raise ValueError(f"unknown engine {engine_kind!r}")


def betweenness_centrality(
    graph: Graph,
    batch_size: int = 32,
    heuristics: str = "h0",
    engine_kind: str = "dense",
    num_levels: int | None = None,
    jit: bool = True,
    ledger=None,
    checkpoint=None,
    overlap: str = "none",
    straggler: str = "none",
    sampling: str = "off",
    sample_frac: float | None = None,
    sample_k: int | None = None,
    sample_seed: int = 0,
    stop_rule=None,
    weighted: bool = False,
    delta: float | None = None,
) -> BCResult:
    """Exact or source-sampled BC of an undirected graph
    (paper conventions: unnormalized, both traversal directions counted).

    Args:
      graph:       input graph.
      batch_size:  concurrent sources per round (multi-source width).
      heuristics:  "h0" | "h1" | "h2" | "h3" (paper Fig. 12 naming).
      weighted:    run the bucketed (delta-stepping) weighted traversal;
                   requires ``graph.w`` (``Graph.from_edges(weights=)``)
                   and restricts ``heuristics`` to
                   :data:`WEIGHTED_HEURISTICS`.  False on a weighted
                   graph ignores the weights (unit-distance BC).
      delta:       bucket width Δ for the weighted traversal; None derives
                   it from the edge-weight statistics
                   (:func:`repro.core.operators.auto_delta`).
      engine_kind: "dense" (n×n matmul) | "sparse" (segment-sum) |
                   "pallas" / "pallas_bf16" (fused level kernels).
      num_levels:  optional static level bound (compile-friendly); must be
                   ≥ graph diameter + 1 when given.
      jit:         wrap the round function in jax.jit (disable to debug).
      ledger:      optional RoundLedger — committed rounds are skipped
                   (in-memory exactly-once, e.g. speculative re-execution).
      checkpoint:  optional fault_tolerance.BCCheckpoint — durable
                   kill-and-resume (launch/bc.py --ckpt-dir).
      overlap:     collective-schedule policy, accepted for protocol
                   uniformity with the distributed entry point; a single
                   device has no collectives to overlap, so only "none"
                   is valid here.
      straggler:   sub-cluster scheduling policy, accepted for protocol
                   uniformity; a single device has no replicas to steal
                   from or re-deal to, so only "none" is valid here.
      sampling:    :data:`repro.serving.SAMPLING_MODES` — "off" (exact),
                   "fixed" (seeded k-root subset, result rescaled by
                   N/k) or "adaptive" (additionally stops dispatching
                   once top-k ranks stabilize; see
                   :class:`repro.serving.AdaptiveStopRule`).  Sampling
                   requires ``heuristics="h0"`` (per-root additivity).
      sample_frac / sample_k: sample size as a fraction of — or count
                   within — the eligible roots (at most one of the two;
                   ``sample_frac=1.0`` reproduces the unsampled schedule
                   exactly).
      sample_seed: RNG seed of the root draw (same seed ⇒ nested samples
                   in k).
      stop_rule:   explicit ``BCDriver`` stop-rule override, e.g.
                   :class:`repro.serving.BlockBudgetStop` for serving
                   refresh slices; default under "adaptive" is
                   ``AdaptiveStopRule()``.  Requires ``sampling != "off"``
                   — a truncated run is only meaningful as a rescaled
                   estimate.
    """
    from repro.serving.sampling import (
        AdaptiveStopRule,
        eligible_roots,
        plan_sampling,
    )

    if normalize_overlap(overlap) != "none":
        raise ValueError(
            "overlap schedules are a distributed-engine feature; "
            "single-device engines have no collectives to pipeline"
        )
    if normalize_straggler(straggler) != "none":
        raise ValueError(
            "straggler scheduling is a sub-cluster feature; a single "
            "device has no replicas to steal rounds from or re-deal to"
        )
    plan = plan_sampling(
        eligible_roots(graph), sampling, sample_frac, sample_k, sample_seed
    )
    if plan.mode != "off" and heuristics != "h0":
        raise ValueError(
            "sampling requires heuristics='h0': the 1-/2-degree analytic "
            "corrections are not per-root additive, so a sampled run "
            "could not be rescaled into an unbiased estimator"
        )
    if stop_rule is not None and plan.mode == "off":
        raise ValueError(
            "a stop_rule truncates the schedule, which is only meaningful "
            "as a rescaled estimate; pass sampling='fixed' or 'adaptive'"
        )
    if plan.mode == "adaptive" and stop_rule is None:
        stop_rule = AdaptiveStopRule()
    if weighted:
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
        if delta is None:
            delta = auto_delta(graph)
        if not (float(delta) > 0 and np.isfinite(delta)):
            raise ValueError(f"delta must be positive and finite, got {delta}")
    elif delta is not None:
        raise ValueError("delta is only meaningful with weighted=True")
    n = graph.n
    schedule, prep, residual, omega_i = build_schedule(
        graph, batch_size=batch_size, heuristics=heuristics, roots=plan.roots
    )
    omega = jnp.asarray(omega_i, jnp.float32)

    if weighted:
        operands, make_op = _make_weighted_operator_fn(
            residual, n, engine_kind, float(delta)
        )
    else:
        operands, make_op = _make_operator_fn(residual, n, engine_kind)

    def block_fn(operands, omega, sources, derived):
        # [1, s], [1, k, 3] -> block-dim outputs
        round_fn = make_round_fn(
            lambda: make_op(*operands), n, num_levels=num_levels
        )
        bc_r, ns, roots, levels = round_fn(sources[0], derived[0], omega)
        return bc_r, ns[None], roots[None], levels[None]

    if jit:
        block_fn = jax.jit(block_fn)

    driver = BCDriver(
        functools.partial(block_fn, operands, omega), schedule, n=n,
        prep=prep, ledger=ledger, checkpoint=checkpoint, stop_rule=stop_rule,
    )
    result = driver.run()
    return apply_sampling_rescale(result, plan)


def apply_sampling_rescale(result: BCResult, plan) -> BCResult:
    """Rescale a sampled run's BC by N / roots_accumulated (in place).

    Shared by both entrypoints.  The denominator is what the driver
    *committed* — an adaptive stop truncates it below ``plan.k``, a full
    fixed run equals it — so fixed and adaptive share one calibration.
    Checkpoints always store the raw accumulator (the driver snapshots
    before this runs), so a resumed run re-applies the then-current
    scale to the grown prefix — rescale and resume commute.
    """
    if plan.mode == "off":
        return result
    denom = result.roots_accumulated
    scale = plan.num_eligible / denom if denom else 1.0
    if scale != 1.0:
        result.bc = result.bc * scale
    result.sampling_stats = {
        "mode": plan.mode,
        "seed": plan.seed,
        "num_eligible": plan.num_eligible,
        "k_planned": plan.k,
        "roots_accumulated": denom,
        "scale": scale,
    }
    return result
