"""BC snapshot-serving launcher: answer queries while sampling refines.

    PYTHONPATH=src python -m repro.launch.serve_bc --rmat-scale 8 \
        --mesh 2x4 --sample-frac 1.0 --refresh-blocks 2 --generations 3 \
        --ckpt-dir /tmp/bc_serve
    PYTHONPATH=src python -m repro.launch.serve_bc --grid 12x12 \
        --sampling adaptive --queries 20

Front end of the sampled-BC stack (repro/serving/): a foreground query
loop answers ``top_k`` / ``score`` requests from the current
:class:`~repro.serving.BCSnapshotStore` generation while a background
refresher thread runs the *same* sampled schedule in budgeted slices —
each slice is one ``distributed_betweenness_centrality`` (or
single-device) run over a shared :class:`BCCheckpoint` with a
:class:`~repro.serving.BlockBudgetStop` stop rule, so resume skips the
committed prefix and every generation strictly extends the evidence.
After each slice the store republishes from the checkpoint's committed
prefix (raw accumulator, rescaled N/k here) and atomically swaps the
generation; the last slice runs without a block budget, so the final
generation is the full sampled estimate (exact when
``--sample-frac 1.0``).

Queries issued mid-refresh are answered from the previous generation
and counted as ``stale_hits`` — the store's stats dict accounts every
query as exactly one of hit / stale_hit / miss.  A killed refresher's
replacement republishes the last *committed* generation at startup
(``publish_from_checkpoint``) before resuming, so serving never
regresses past durable state.
"""
from __future__ import annotations

import argparse
import logging
import os
import threading
import time

import numpy as np

from repro.core import betweenness_centrality
from repro.core.distributed import distributed_betweenness_centrality
from repro.distributed.fault_tolerance import BCCheckpoint
from repro.graphs import grid_graph, rmat_graph, road_like_graph
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import (
    BCSnapshotStore,
    BlockBudgetStop,
    eligible_roots,
    plan_sampling,
)

logger = logging.getLogger(__name__)


def run_serving(
    graph,
    mesh=None,
    *,
    ckpt_path: str,
    batch_size: int = 8,
    engine: str = "sparse",
    overlap: str = "none",
    sampling: str = "fixed",
    sample_frac: float | None = None,
    sample_k: int | None = None,
    sample_seed: int = 0,
    refresh_blocks: int = 2,
    generations: int = 3,
    queries: int = 12,
    top_k: int = 10,
    poll_s: float = 0.02,
) -> dict:
    """Serve BC queries while a background refresher extends the sample.

    Args:
      graph:          input graph.
      mesh:           jax mesh for the distributed path, or None for the
                      single-device driver (same serving semantics).
      ckpt_path:      BCCheckpoint file the refresher slices share — the
                      durable state a replacement refresher resumes from.
      sampling / sample_frac / sample_k / sample_seed: the sampled
                      schedule (see :func:`repro.core.bc
                      .betweenness_centrality`).  ``"off"`` is rejected:
                      budgeted refresh slices are truncated runs, which
                      are only meaningful as rescaled estimates.
      refresh_blocks: dispatch blocks each non-final slice runs before
                      republishing (the refresh cadence).
      generations:    maximum refresher slices; the last runs without a
                      block budget so the final generation is the full
                      sampled estimate.  Slices after the schedule is
                      exhausted are skipped.
      queries:        minimum foreground ``top_k`` queries to issue.
      top_k:          k of the foreground query loop.
      poll_s:         sleep between foreground queries while refreshing.

    Returns a stats dict: per-slice telemetry (``refresh_runs``), the
    store's query accounting (``stats``), the generation history the
    query loop observed (``history``), and the final snapshot's top-k
    and full estimate (``final_top_k`` / ``final_bc``).
    """
    if sampling == "off":
        raise ValueError(
            "serving refreshes in budgeted slices, which are only "
            "meaningful as rescaled estimates; pass sampling='fixed' "
            "(sample_frac=1.0 for an exact final generation) or "
            "'adaptive'"
        )
    plan = plan_sampling(
        eligible_roots(graph), sampling, sample_frac, sample_k, sample_seed
    )
    checkpoint = BCCheckpoint(ckpt_path)
    store = BCSnapshotStore()
    refresh_runs: list[dict] = []
    refresh_errors: list[BaseException] = []

    def _publish(meta: dict) -> int | None:
        return store.publish_from_checkpoint(
            checkpoint, num_eligible=plan.num_eligible, meta=meta
        )

    def _run_slice(stop_rule):
        if mesh is not None:
            kind = "sparse" if engine in ("dense", "sparse") else engine
            return distributed_betweenness_centrality(
                graph,
                mesh,
                replica_axis="pod" if len(mesh.devices.shape) == 3 else None,
                batch_size=batch_size,
                heuristics="h0",
                engine_kind=kind,
                overlap=overlap,
                checkpoint=checkpoint,
                sampling=sampling,
                sample_frac=sample_frac,
                sample_k=sample_k,
                sample_seed=sample_seed,
                stop_rule=stop_rule,
                full_result=True,
            )
        return betweenness_centrality(
            graph,
            batch_size=batch_size,
            heuristics="h0",
            engine_kind=engine,
            checkpoint=checkpoint,
            sampling=sampling,
            sample_frac=sample_frac,
            sample_k=sample_k,
            sample_seed=sample_seed,
            stop_rule=stop_rule,
        )

    # resume path: a replacement refresher serves the last committed
    # generation immediately, before any new rounds run
    if checkpoint.exists():
        gen = _publish({"resumed": True})
        if gen is not None:
            logger.info("resumed serving from committed snapshot (gen %d)", gen)

    def _refresher():
        try:
            for i in range(generations):
                final = i == generations - 1
                store.begin_refresh()
                t0 = time.perf_counter()
                result = _run_slice(
                    None if final else BlockBudgetStop(refresh_blocks)
                )
                _publish(
                    {
                        "refresh_slice": i + 1,
                        "final": not result.stopped_early,
                    }
                )
                store.end_refresh()
                refresh_runs.append(
                    {
                        "slice": i + 1,
                        "rounds_run": result.rounds_run,
                        "roots_accumulated": result.roots_accumulated,
                        "stopped_early": result.stopped_early,
                        "wall_s": time.perf_counter() - t0,
                        "driver_wall_s": result.wall_s,
                        "sampling": result.sampling_stats,
                        "recovery": result.recovery_stats,
                    }
                )
                if not result.stopped_early:
                    break  # schedule exhausted — the estimate is final
        except BaseException as exc:  # surfaced to the caller after join
            refresh_errors.append(exc)
        finally:
            store.end_refresh()

    history: list[dict] = []

    def _query():
        res = store.top_k(top_k)
        if res is None:
            return
        snap, top = res
        if not history or history[-1]["generation"] != snap.generation:
            history.append(
                {
                    "generation": snap.generation,
                    "top_k": [v for v, _ in top],
                    "meta": dict(snap.meta),
                }
            )

    _query()  # cold query: a miss unless a committed snapshot resumed us
    refresher = threading.Thread(target=_refresher, name="bc-refresher")
    refresher.start()
    issued = 1
    while refresher.is_alive() or issued < queries:
        _query()
        issued += 1
        if refresher.is_alive():
            time.sleep(poll_s)
    refresher.join()
    if refresh_errors:
        raise refresh_errors[0]
    _query()  # settled query: always a hit against the final generation

    snap = store.snapshot()
    final_top = history[-1]["top_k"] if history else []
    return {
        "n": graph.n,
        "plan": {
            "mode": plan.mode,
            "num_eligible": plan.num_eligible,
            "k": plan.k,
            "seed": plan.seed,
        },
        "generations_published": store.generation,
        "refresh_runs": refresh_runs,
        "stats": dict(store.stats),
        "history": history,
        "final_top_k": final_top,
        "final_bc": None if snap is None else snap.bc,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rmat-scale", type=int, default=None)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--grid", default=None, help="RxC grid graph")
    ap.add_argument("--road", default=None, help="RxC road-like graph")
    ap.add_argument("--mesh", default=None, help="RxC or FRxRxC device mesh")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--engine", default="sparse")
    ap.add_argument("--overlap", default="none")
    ap.add_argument("--sampling", default="fixed", choices=["fixed", "adaptive"])
    ap.add_argument("--sample-frac", type=float, default=None)
    ap.add_argument("--sample-k", type=int, default=None)
    ap.add_argument("--sample-seed", type=int, default=0)
    ap.add_argument("--refresh-blocks", type=int, default=2)
    ap.add_argument("--generations", type=int, default=3)
    ap.add_argument("--queries", type=int, default=12)
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None, help="shared refresher state")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO, format="%(name)s: %(message)s")
    enable_compile_cache()

    if args.rmat_scale is not None:
        graph = rmat_graph(args.rmat_scale, args.edge_factor, seed=1)
        name = f"rmat_s{args.rmat_scale}_ef{args.edge_factor}"
    elif args.grid:
        r, c = map(int, args.grid.split("x"))
        graph = grid_graph(r, c)
        name = f"grid_{r}x{c}"
    elif args.road:
        r, c = map(int, args.road.split("x"))
        graph = road_like_graph(r, c, seed=1)
        name = f"road_{r}x{c}"
    else:
        raise SystemExit("pick --rmat-scale, --grid or --road")

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh

        shape = tuple(map(int, args.mesh.split("x")))
        mesh = make_mesh(shape, ("pod", "data", "model")[-len(shape):])

    ckpt_dir = args.ckpt_dir or os.path.join("/tmp", "bc_serve")
    os.makedirs(ckpt_dir, exist_ok=True)
    out = run_serving(
        graph,
        mesh,
        ckpt_path=os.path.join(ckpt_dir, f"{name}.npz"),
        batch_size=args.batch_size,
        engine=args.engine,
        overlap=args.overlap,
        sampling=args.sampling,
        sample_frac=args.sample_frac,
        sample_k=args.sample_k,
        sample_seed=args.sample_seed,
        refresh_blocks=args.refresh_blocks,
        generations=args.generations,
        queries=args.queries,
        top_k=args.top,
    )

    print(
        f"{name}: n={out['n']} sampling={out['plan']['mode']} "
        f"k={out['plan']['k']}/{out['plan']['num_eligible']} roots"
    )
    for run in out["refresh_runs"]:
        print(
            f"  slice {run['slice']}: {run['rounds_run']} rounds, "
            f"{run['roots_accumulated']} roots committed, "
            f"{'stopped early' if run['stopped_early'] else 'final'}, "
            f"{run['wall_s']:.2f}s"
        )
    st = out["stats"]
    print(
        f"served {st['queries']} queries across "
        f"{out['generations_published']} generations: {st['hits']} hits, "
        f"{st['stale_hits']} stale, {st['misses']} misses"
    )
    bc = out["final_bc"]
    for v in out["final_top_k"]:
        print(f"  v{int(v):>8d}  BC = {bc[int(v)]:.1f}")


if __name__ == "__main__":
    main()
