"""BC launcher: exact betweenness centrality with MGBC.

    PYTHONPATH=src python -m repro.launch.bc --rmat-scale 10 --edge-factor 8 \
        --heuristics h3 --batch-size 32
    PYTHONPATH=src python -m repro.launch.bc --grid 40x40 --heuristics h1 \
        --mesh 2x4 --engine pallas --ckpt-dir /tmp/bc_ckpt
    PYTHONPATH=src python -m repro.launch.bc --rmat-scale 8 --mesh 2x2x2 \
        --overlap expand --straggler redeal
    PYTHONPATH=src python -m repro.launch.bc --road 20x20 --weights dyadic \
        --weighted --mesh 2x4 --engine pallas

``--weighted`` swaps the level-synchronous traversal for the bucketed
weighted one (distance buckets of width ``--delta``, auto-derived when
unset); ``--weights unit|dyadic`` samples edge weights onto the
generated graph (dyadic = k/4, k=1..16 — exactly representable, so f32
distance sums are exact).  Weighted runs restrict ``--heuristics`` to
the weight-sound modes (h0/h1/h1t).

Supports single-device and distributed execution; every engine of the
unified traversal stack is selectable with ``--engine`` (single-device:
``dense | sparse | pallas | pallas_bf16``; distributed: the ``sparse``
arc-list engine, the Pallas dense-block engines, the blocked-sparse
``pallas_sparse`` engine for graphs whose dense blocks do not fit, or
``pallas_hybrid``, which picks dense vs BCSR *per device cell* from the
roofline's bytes-streamed threshold — ``--hybrid-threshold`` overrides
the break-even, the per-cell choice is logged).

``--mesh RxC`` runs one 2-D-decomposed traversal grid; ``--mesh FRxRxC``
(three dims) replicates that grid into ``FR`` sub-clusters (paper §3.3),
each processing different source rounds concurrently.

``--heuristics`` selects the preprocessing (paper §3.4 / Fig. 12 naming;
see core/heuristics/): ``h0`` none | ``h1`` 1-degree reduction |
``h2`` 2-degree DMF | ``h3`` both | ``h1t``/``h3t`` exhaustive
pendant-tree contraction (beyond-paper).

``--overlap`` selects the distributed collective schedule: ``none``
(barrier all_gather/psum_scatter), ``expand`` (ring-pipelined gather),
``expand+fold`` (both collectives decomposed into ppermute rings
overlapped with block compute — paper Fig. 2) or ``auto`` (picked from
the roofline's pipelining estimate and logged).

``--straggler`` selects the sub-cluster scheduling policy (needs a
three-dim ``--mesh``): ``none`` static deal | ``steal`` idle replicas
pull rounds from the heaviest backlog (+ speculative tail backups) |
``redeal`` pending rounds are re-packed across replicas when one
replica's EWMA per-round wall exceeds ``--straggler-factor ×`` the
fastest's.  Commits stay exactly-once across steals, re-deals and
kill-and-resume (per-replica round ledgers, first commit wins).

``--autotune`` swaps the roofline guesses behind the tile, hybrid-cell,
``--overlap auto`` and straggler-prior choices for cached measurements
(``off`` roofline-only | ``cache`` consult, never measure | ``measure``
micro-bench on a miss and record), persisted across runs via
``--autotune-cache PATH``; it also packs rounds by sampled root
eccentricity so depth-divergent roots stop sharing a batch.

``--chaos PLAN`` injects a deterministic fault plan at the round and
file-write seams (``kind@at[xcount][:arg]`` entries: ``transient``,
``poison``, ``kill:rI``, ``crash``, ``torn``, ``cache``, ``flip``
(finite silent corruption), ``stall`` (delay a dispatch) — see
distributed/chaos.py) so any failure is reproducible from the CLI; the
driver's self-healing (``--max-retries`` / ``--retry-backoff`` retry
budget, ``--numeric-guard`` non-finite quarantine, replica-loss re-mesh
under a straggler policy) recovers and reports what it did.
``--generations`` keeps that many rotated BCCheckpoint snapshots so a
torn newest write falls back instead of cold-starting.

``--integrity`` makes every round self-verifying (needs ``--mesh``):
``audit`` cross-checks each drained block against its in-graph claimed
sum plus output-domain invariants; ``checksum`` additionally threads an
ABFT column-sum lane through every level SpMM, catching silent data
corruption (e.g. ``--chaos 'flip@K'``) that is finite and so invisible
to the numeric guard.  A failed audit quarantines and re-dispatches the
block; under ``--straggler steal`` duplicated tail rounds are also
compared lane-vs-lane (duplicate-vote SDC detection) with a tie-breaker
re-dispatch on mismatch.  ``--dispatch-deadline SECONDS|auto`` arms the
dispatch watchdog: a block exceeding its deadline (``auto`` derives one
from the roofline/autotune round prior) is re-dispatched and, when the
retry budget is spent, escalated to a replica loss that the elastic
re-mesh absorbs — a wedged replica can no longer hang the job.

The per-device adjacency + state footprint is reported before
compiling; ``--hbm-gb <GiB>`` additionally arms the fail-fast memory
guard, turning an over-budget engine into an immediate error with a
suggestion (``pallas_sparse`` / a larger mesh) instead of an OOM
mid-round.  ``--ckpt-dir`` snapshots (partial BC, n_s, committed
rounds) through a BCCheckpoint — a killed job resumes at the first
uncommitted round — and TEPS is reported per paper Eq. 7.
"""
from __future__ import annotations

import argparse
import logging
import os
import time

import numpy as np

from repro.autotune import AUTOTUNE_MODES
from repro.core import betweenness_centrality
from repro.core.bc import ENGINE_KINDS
from repro.core.driver import INTEGRITY_MODES, STRAGGLER_POLICIES
from repro.core.operators import OVERLAP_POLICIES
from repro.core.scheduler import HEURISTICS_MODES
from repro.core.distributed import (
    DIST_ENGINE_KINDS,
    distributed_betweenness_centrality,
)
from repro.distributed.fault_tolerance import BCCheckpoint
from repro.graphs import grid_graph, rmat_graph, road_like_graph
from repro.graphs.generators import WEIGHT_MODES, weighted_copy
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import SAMPLING_MODES


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rmat-scale", type=int, default=None)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--grid", default=None, help="RxC grid graph")
    ap.add_argument("--road", default=None, help="RxC road-like graph")
    ap.add_argument("--heuristics", default="h0", choices=list(HEURISTICS_MODES))
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument(
        "--engine",
        default="dense",
        choices=sorted(set(ENGINE_KINDS) | set(DIST_ENGINE_KINDS)),
    )
    ap.add_argument(
        "--mesh",
        default=None,
        help="distributed device mesh: RxC (one 2-D grid) or FRxRxC "
        "(FR sub-cluster replicas of an RxC grid, paper §3.3)",
    )
    ap.add_argument(
        "--overlap",
        default="none",
        choices=list(OVERLAP_POLICIES) + ["auto"],
        help="distributed collective schedule (ring pipelining; needs --mesh; "
        "'auto' picks from the roofline estimate)",
    )
    ap.add_argument(
        "--tile",
        default=None,
        help="blocked-sparse tile shape BM or BMxBK (pallas_sparse / "
        "pallas_hybrid; both must divide the partition chunk; default: "
        "largest lane-friendly divisor <= 128).  Coarser tiles push "
        "more hybrid cells over the dense break-even",
    )
    ap.add_argument(
        "--hybrid-threshold",
        type=float,
        default=1.0,
        help="pallas_hybrid break-even: a cell streams BCSR tiles when "
        "their bytes are under this fraction of its dense-block bytes "
        "(0 forces all cells dense, a large value all sparse; the "
        "per-cell choice is logged)",
    )
    ap.add_argument(
        "--hbm-gb",
        type=float,
        default=0.0,
        help="per-device HBM budget (GiB) arming the fail-fast memory "
        "guard (e.g. 16 for v5e); the footprint is always reported, but "
        "only an explicit budget turns it into a pre-compile error",
    )
    ap.add_argument(
        "--straggler",
        default="none",
        choices=list(STRAGGLER_POLICIES),
        help="sub-cluster straggler policy (needs a FRxRxC --mesh): "
        "'steal' pulls rounds into replicas whose queue ran dry; "
        "'redeal' re-packs all pending rounds when one replica's EWMA "
        "per-round wall exceeds --straggler-factor x the fastest's",
    )
    ap.add_argument(
        "--straggler-factor",
        type=float,
        default=2.0,
        help="EWMA per-round-wall ratio over the fastest replica that "
        "triggers a re-deal (straggler=redeal only; steal is "
        "queue-driven and ignores it)",
    )
    ap.add_argument(
        "--autotune",
        default="off",
        choices=list(AUTOTUNE_MODES),
        help="measured-cost autotuning (needs --mesh): 'cache' consults "
        "the measured-cost cache and falls back to the roofline on a "
        "miss; 'measure' micro-benches candidate configs on a miss and "
        "records them (measure-once — the next run with the same graph "
        "stats + mesh hits the cache).  Also switches the scheduler to "
        "eccentricity-packed rounds",
    )
    ap.add_argument(
        "--autotune-cache",
        default=None,
        help="path of the persistent measured-cost cache JSON "
        "(default: in-memory for this run only)",
    )
    ap.add_argument(
        "--chaos",
        default=None,
        help="deterministic fault-injection plan (needs --mesh): "
        "'kind@at[xcount][:arg]' entries separated by ';', plus 'seed=N' "
        "— kinds transient | poison[:nan|:inf] | kill:rI | crash | torn "
        "| cache | flip[:rI|:dI|:neg] (finite silent corruption; pair "
        "with --integrity) | stall[:MS] (delay a dispatch; pair with "
        "--dispatch-deadline), e.g. "
        "'seed=7;transient@1x2;poison@3:nan;kill@4:r1;flip@5'. "
        "Reproduces any failure from the CLI; recovery is reported "
        "(see distributed/chaos.py)",
    )
    ap.add_argument(
        "--integrity",
        default="off",
        choices=list(INTEGRITY_MODES),
        help="self-verifying rounds (needs --mesh): 'audit' cross-checks "
        "each drained block against its claimed sum + output-domain "
        "invariants; 'checksum' adds the ABFT column-sum lane through "
        "every level SpMM (catches finite silent corruption the "
        "numeric guard cannot see).  Failed blocks are quarantined and "
        "re-dispatched; detection counters are reported",
    )
    ap.add_argument(
        "--dispatch-deadline",
        default=None,
        help="dispatch watchdog deadline in seconds, or 'auto' to derive "
        "one from the roofline/autotune round prior (needs --mesh).  A "
        "block exceeding it is re-dispatched, then escalated to a "
        "replica loss the elastic re-mesh absorbs",
    )
    ap.add_argument(
        "--max-retries",
        type=int,
        default=None,
        help="self-healing retry budget per dispatch block (transient "
        "errors + quarantined non-finite blocks; default 2)",
    )
    ap.add_argument(
        "--retry-backoff",
        type=float,
        default=None,
        help="base seconds of the exponential backoff between transient "
        "retries (default 0.05)",
    )
    ap.add_argument(
        "--numeric-guard",
        action="store_true",
        help="force the post-block non-finite bc/ns guard on (adds a "
        "per-block host sync on the static fast path; it is automatic "
        "wherever the loop already syncs — profile/straggler modes — "
        "and whenever a fallback path exists)",
    )
    ap.add_argument(
        "--generations",
        type=int,
        default=None,
        help="BCCheckpoint snapshot generations to keep (default 3); "
        "load falls back to the newest intact one on a torn write",
    )
    ap.add_argument(
        "--sampling",
        default="off",
        choices=list(SAMPLING_MODES),
        help="source-sampled approximate BC: 'fixed' runs a seeded "
        "k-root subset and rescales by N/k; 'adaptive' additionally "
        "stops dispatching once the top-k rank set stabilizes across "
        "consecutive blocks.  Needs --heuristics h0 (per-root "
        "additivity); --sample-frac 1.0 reproduces the exact schedule",
    )
    ap.add_argument(
        "--sample-frac",
        type=float,
        default=None,
        help="sample size as a fraction of the eligible roots "
        "(mutually exclusive with --sample-k)",
    )
    ap.add_argument(
        "--sample-k",
        type=int,
        default=None,
        help="sample size as a root count (mutually exclusive with "
        "--sample-frac)",
    )
    ap.add_argument(
        "--sample-seed",
        type=int,
        default=0,
        help="seed of the root draw; the same seed gives nested "
        "samples as k grows (serving refinement extends evidence)",
    )
    ap.add_argument(
        "--weighted",
        action="store_true",
        help="weighted BC via the bucketed (delta-stepping-style) "
        "traversal instead of the level-synchronous loop.  Needs edge "
        "weights on the graph: pass --weights to sample them on the "
        "generated graph.  Restricts --heuristics to the weight-sound "
        "modes (h0/h1/h1t)",
    )
    ap.add_argument(
        "--weights",
        default="none",
        choices=list(WEIGHT_MODES),
        help="edge-weight mode of the generated graph: 'unit' (all 1.0; "
        "reproduces the unweighted run exactly at --delta 1) or 'dyadic' "
        "(k/4, k=1..16 — exactly representable, so distance sums are "
        "exact in f32).  Implies nothing by itself; pair with --weighted",
    )
    ap.add_argument(
        "--delta",
        type=float,
        default=None,
        help="bucket width of the weighted traversal (needs --weighted; "
        "default: derived from the weight distribution, see "
        "repro.core.operators.auto_delta)",
    )
    ap.add_argument("--ckpt-dir", default=None, help="round-ledger resume dir")
    ap.add_argument("--out", default=None)
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO, format="%(name)s: %(message)s")
    enable_compile_cache()

    if args.rmat_scale is not None:
        graph = rmat_graph(
            args.rmat_scale, args.edge_factor, seed=1, weights=args.weights
        )
        name = f"rmat_s{args.rmat_scale}_ef{args.edge_factor}"
    elif args.grid:
        r, c = map(int, args.grid.split("x"))
        graph = grid_graph(r, c)
        if args.weights != "none":
            graph = weighted_copy(graph, weights=args.weights, seed=1)
        name = f"grid_{r}x{c}"
    elif args.road:
        r, c = map(int, args.road.split("x"))
        graph = road_like_graph(r, c, seed=1, weights=args.weights)
        name = f"road_{r}x{c}"
    else:
        raise SystemExit("pick --rmat-scale, --grid or --road")

    if args.weighted and graph.w is None:
        raise SystemExit(
            "--weighted needs edge weights; pass --weights unit|dyadic"
        )
    if args.delta is not None and not args.weighted:
        raise SystemExit("--delta sizes the weighted buckets; pass --weighted")

    checkpoint = None
    if args.ckpt_dir:
        os.makedirs(args.ckpt_dir, exist_ok=True)
        ckpt_kw = {} if args.generations is None else {"generations": args.generations}
        checkpoint = BCCheckpoint(
            os.path.join(args.ckpt_dir, f"{name}.npz"), **ckpt_kw
        )
        if checkpoint.exists():
            _, _, committed = checkpoint.load()
            gen = checkpoint.loaded_generation
            print(
                f"resuming: {len(committed)} rounds already committed"
                + ("" if not gen else f" (from fallback generation {gen})")
            )

    if args.overlap != "none" and not args.mesh:
        raise SystemExit("--overlap is a distributed schedule; pass --mesh RxC")
    if args.engine in ("pallas_sparse", "pallas_hybrid") and not args.mesh:
        raise SystemExit(
            f"{args.engine} is a distributed engine; pass --mesh RxC"
        )
    tile = None
    if args.tile:
        if not args.mesh:
            raise SystemExit(
                "--tile shapes the blocked-sparse/hybrid layouts; pass --mesh RxC"
            )
        try:
            dims = tuple(int(d) for d in args.tile.split("x"))
        except ValueError:
            dims = ()
        if len(dims) not in (1, 2) or any(d <= 0 for d in dims):
            raise SystemExit("--tile takes BM or BMxBK (positive integers)")
        tile = (dims[0], dims[-1])
    mesh_shape = tuple(map(int, args.mesh.split("x"))) if args.mesh else None
    if mesh_shape is not None and len(mesh_shape) not in (2, 3):
        raise SystemExit("--mesh takes RxC or FRxRxC")
    if args.straggler != "none" and (mesh_shape is None or len(mesh_shape) != 3):
        raise SystemExit(
            "--straggler re-deals rounds between sub-cluster replicas; "
            "pass a replicated --mesh FRxRxC"
        )
    if args.autotune != "off" and not args.mesh:
        raise SystemExit(
            "--autotune measures distributed round configs; pass --mesh RxC"
        )
    if args.chaos and not args.mesh:
        raise SystemExit(
            "--chaos injects faults at the distributed round seam; "
            "pass --mesh RxC"
        )
    if args.integrity != "off" and not args.mesh:
        raise SystemExit(
            "--integrity audits the distributed round loop; pass --mesh RxC"
        )
    deadline = None
    if args.dispatch_deadline is not None:
        if not args.mesh:
            raise SystemExit(
                "--dispatch-deadline arms the distributed dispatch "
                "watchdog; pass --mesh RxC"
            )
        if args.dispatch_deadline == "auto":
            deadline = "auto"
        else:
            try:
                deadline = float(args.dispatch_deadline)
            except ValueError:
                raise SystemExit("--dispatch-deadline takes seconds or 'auto'")

    sampling_kw: dict = {}
    if args.sampling != "off":
        sampling_kw = {
            "sampling": args.sampling,
            "sample_frac": args.sample_frac,
            "sample_k": args.sample_k,
            "sample_seed": args.sample_seed,
        }
    elif args.sample_frac is not None or args.sample_k is not None:
        raise SystemExit(
            "--sample-frac/--sample-k size a sampled run; pass "
            "--sampling fixed|adaptive"
        )

    print(
        f"{name}: n={graph.n} m={graph.num_edges} "
        f"heuristics={args.heuristics} engine={args.engine} "
        f"overlap={args.overlap} straggler={args.straggler} "
        f"sampling={args.sampling}"
        + (f" weighted(delta={args.delta or 'auto'})" if args.weighted else "")
    )
    t0 = time.time()
    if mesh_shape is not None:
        from repro.launch.mesh import make_mesh

        axes = ("pod", "data", "model")[-len(mesh_shape):]
        mesh = make_mesh(mesh_shape, axes)
        # the distributed engine's arc-list local compute is the sparse
        # path; dense-block MXU compute is the pallas pair.
        engine_kind = "sparse" if args.engine in ("dense", "sparse") else args.engine
        robust_kw: dict = {}
        if args.max_retries is not None:
            robust_kw["max_retries"] = args.max_retries
        if args.retry_backoff is not None:
            robust_kw["retry_backoff_s"] = args.retry_backoff
        if args.numeric_guard:
            robust_kw["numeric_guard"] = True
        if args.integrity != "off":
            robust_kw["integrity"] = args.integrity
        if deadline is not None:
            robust_kw["dispatch_deadline_s"] = deadline
        result = distributed_betweenness_centrality(
            graph,
            mesh,
            replica_axis="pod" if len(mesh_shape) == 3 else None,
            batch_size=args.batch_size,
            heuristics=args.heuristics,
            engine_kind=engine_kind,
            overlap=args.overlap,
            tile=tile,
            hybrid_threshold=args.hybrid_threshold,
            hbm_limit_bytes=args.hbm_gb * 2**30 if args.hbm_gb > 0 else None,
            checkpoint=checkpoint,
            straggler=args.straggler,
            straggler_factor=args.straggler_factor,
            autotune=args.autotune,
            autotune_cache=args.autotune_cache,
            chaos=args.chaos,
            full_result=True,
            weighted=args.weighted,
            delta=args.delta,
            **robust_kw,
            **sampling_kw,
        )
        bc, schedule = result.bc, result.schedule
        rounds = len(schedule.rounds)
        samp = result.sampling_stats
        rec = result.recovery_stats or {}
        integ = rec.get("integrity") or {}
        # the integrity sub-dict is informational even when healthy (its
        # "mode" string and checksum residual are always truthy under
        # integrity=checksum) — only its detection counters are events
        integ_events = {
            k: v
            for k, v in integ.items()
            if k not in ("mode", "max_checksum_residual") and v
        }
        if args.chaos or any(
            v
            for k, v in rec.items()
            if k not in ("resumed_generation", "integrity") and v
        ) or integ_events or rec.get("resumed_generation"):
            print(
                "recovery: "
                f"{rec.get('retries', 0)} retries "
                f"({rec.get('transient_errors', 0)} transient), "
                f"{rec.get('quarantined_blocks', 0)} quarantined, "
                f"{rec.get('fallback_recomputes', 0)} fallback recomputes, "
                f"{rec.get('remesh_events', 0)} re-mesh events "
                f"(dead replicas {rec.get('dead_replicas', [])}), "
                f"resumed generation {rec.get('resumed_generation')}"
            )
        if integ and integ.get("mode", "off") != "off":
            print(
                f"integrity[{integ['mode']}]: "
                f"{integ.get('checksum_failures', 0)} checksum + "
                f"{integ.get('audit_failures', 0)} audit failures, "
                f"{integ.get('vote_mismatches', 0)}/{integ.get('votes', 0)} "
                f"duplicate-vote mismatches, "
                f"{integ.get('quarantined_rounds', 0)} quarantined rounds, "
                f"watchdog {integ.get('watchdog_trips', 0)} trips / "
                f"{integ.get('watchdog_escalations', 0)} escalations, "
                f"max checksum residual "
                f"{integ.get('max_checksum_residual', 0.0):.2e}"
            )
    else:
        res = betweenness_centrality(
            graph,
            batch_size=args.batch_size,
            heuristics=args.heuristics,
            engine_kind=args.engine,
            checkpoint=checkpoint,
            weighted=args.weighted,
            delta=args.delta,
            **sampling_kw,
        )
        bc, rounds = res.bc, res.rounds_run
        samp = res.sampling_stats
    dt = time.time() - t0
    teps = graph.num_edges * graph.n / max(dt, 1e-9)
    print(f"done in {dt:.2f}s — {rounds} rounds, {teps/1e9:.3f} GTEPS_bc")
    if samp:
        print(
            f"sampling[{samp['mode']}]: "
            f"{samp['roots_accumulated']}/{samp['num_eligible']} roots "
            f"(planned k={samp['k_planned']}, seed {samp['seed']}), "
            f"estimates rescaled x{samp['scale']:.3f}"
        )
    top = np.argsort(bc)[::-1][: args.top]
    for v in top:
        print(f"  v{int(v):>8d}  BC = {bc[int(v)]:.1f}")
    if args.out:
        np.save(args.out, bc)
        print("scores ->", args.out)


if __name__ == "__main__":
    main()
