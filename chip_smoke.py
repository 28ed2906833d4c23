"""One chip run of the exact-BC main path on a TPU v5e — not a benchmark.

Deployment: the paper's R-MAT workload (``configs/bc_rmat.py``): Graph500
generator parameters a, b, c = 0.57, 0.19, 0.19, edge factor 16,
``rmat_graph(..., seed=1)``.  Cut from SCALE 23 to SCALE 15 (n = 32768,
about 0.9M arcs after de-duplication): the largest graph whose dense f32
adjacency (4 GiB, a quarter of the chip's 16 GB HBM) the single-device
``pallas`` engine holds together with its round state.  ``--mesh 2x2``
runs SCALE 16 (n = 65536) across the four chips of one host.

Phases, all on the same graph and the same seeded sample of 128 roots
(``sampling="fixed"``, ``heuristics="h0"``, batch 128 = one full round
at the MXU width):

  a) ``betweenness_centrality`` with ``engine_kind="pallas"``, then
     ``"pallas_bf16"`` (single device);
  b) ``distributed_betweenness_centrality`` on a 1x1 ``("data", "model")``
     mesh with ``engine_kind="pallas_sparse"``;
  c) ``launch.serve_bc.run_serving`` for one generation on the same 1x1
     mesh, answering a few top-k queries.

(a) and (b) are checked against the Brandes oracle
(``core/brandes_ref``) summed over the sampled roots, before the N/k
rescale, at rtol 1e-5; (c) is checked against (b).  Every phase must run
its Pallas kernels compiled (its lowered round contains
``tpu_custom_call``) and without any retry, quarantine, fallback
recompute or re-mesh.  Each phase prints its set-up, compile and run
seconds, the device's ``peak_bytes_in_use`` and its recovery counters.

``--mesh 2x2`` runs only the paper's 2-D decomposition:
``distributed_betweenness_centrality`` on a 2x2 mesh with
``engine_kind="pallas_sparse"``, ``overlap="expand+fold"``, checked
against the oracle, and checks that each of the four devices held its
own adjacency shard.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any mismatch, exception or non-TPU platform exits non-zero before it.

    python3 chip_smoke.py                  # one chip, phases a-c
    python3 chip_smoke.py --mesh 2x2       # four chips, 2-D path only
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse [--mesh 2x2]
        # SCALE 8 on the CPU backend with interpreted kernels: checks the
        # phases' paths and results, not the chip (no tpu_custom_call)
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import tempfile
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent

SCALE = 15
SCALE_2X2 = 16
REHEARSAL_SCALE = 8
EDGE_FACTOR = 16
GRAPH_SEED = 1
BATCH = 128
SAMPLE_K = 128
SAMPLE_SEED = 0
RTOL = 1e-5
ATOL = 1e-5  # BC units; the sampled scores reach ~1e6 at SCALE 15
TOP_K = 10
QUERIES = 4

#: JAX's compile-time events: tracing, lowering, and the backend compile
#: (which includes a persistent-cache read on a hit)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(AssertionError):
    """A phase produced a wrong result or ran on the wrong path."""


class CompileClock:
    """Host seconds spent compiling, and persistent-cache hits.

    Compile events nest (tracing an outer jit traces the jitted kernel
    wrappers inside it), so the clock keeps the union of their time
    spans rather than the sum of their durations.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: list[tuple[float, float]] = []
        self.cache_hits = 0

    def on_span(self, event: str, start: float, end: float, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self._spans.append((start, end))

    def on_event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def seconds_since(self, t0: float) -> float:
        """Length of the union of compile spans that started after t0."""
        with self._lock:
            spans = sorted(s for s in self._spans if s[0] >= t0)
        total, cur_start, cur_end = 0.0, None, None
        for start, end in spans:
            if cur_end is None or start > cur_end:
                if cur_end is not None:
                    total += cur_end - cur_start
                cur_start, cur_end = start, end
            else:
                cur_end = max(cur_end, end)
        if cur_end is not None:
            total += cur_end - cur_start
        return total


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--mesh", choices=["2x2"], default=None,
        help="run only the four-chip 2-D decomposition phase",
    )
    ap.add_argument(
        "--rehearse", action="store_true",
        help=f"SCALE {REHEARSAL_SCALE} on any backend, kernels interpreted "
        "on the CPU; skips the compiled-kernel check",
    )
    return ap.parse_args(argv)


def recovery_events(stats: dict) -> dict:
    """The recovery counters of a BCResult that record an event."""
    keys = (
        "retries", "transient_errors", "quarantined_blocks",
        "fallback_recomputes", "remesh_events", "dead_replicas",
    )
    events = {k: stats[k] for k in keys if stats.get(k)}
    for k, v in (stats.get("integrity") or {}).items():
        if k not in ("mode", "max_checksum_residual", "vote_verdicts") and v:
            events[f"integrity.{k}"] = v
    return events


def recovery_counters(stats: dict) -> dict:
    return {
        k: stats.get(k)
        for k in (
            "retries", "transient_errors", "quarantined_blocks",
            "fallback_recomputes", "remesh_events",
        )
    }


def oracle_sum(graph, roots):
    """Σ over ``roots`` of the Brandes dependencies δ_s (δ_s(s) = 0)."""
    import numpy as np

    from repro.core.brandes_ref import single_source_dependencies_csr

    row_ptr, col = graph.csr()
    total = np.zeros(graph.n, np.float64)
    for s in roots:
        delta, _, _ = single_source_dependencies_csr(row_ptr, col, int(s))
        delta[int(s)] = 0.0
        total += delta
    return total


def check_close(name: str, got, want, rtol: float = RTOL, atol: float = ATOL):
    """Raise unless |got - want| <= atol + rtol·|want| everywhere."""
    import numpy as np

    err = np.abs(got - want)
    bad = err > atol + rtol * np.abs(want)
    nz = want != 0
    max_rel = float((err[nz] / np.abs(want[nz])).max()) if nz.any() else 0.0
    if bad.any():
        i = int(np.argmax(err - rtol * np.abs(want)))
        raise SmokeFailure(
            f"{name}: {int(bad.sum())} of {got.size} scores off the "
            f"reference (worst v{i}: got {got[i]!r}, want {want[i]!r}; "
            f"max relative error {max_rel:.3e} > rtol {rtol:g})"
        )
    return max_rel


class Phase:
    """Times one phase and records the programs it compiled."""

    def __init__(self, name: str, clock: CompileClock, dump_root: pathlib.Path):
        self.name = name
        self.clock = clock
        self.dump = dump_root / name.replace("/", "_")
        self.record: dict = {"phase": name}

    def __enter__(self):
        import jax

        self.dump.mkdir(parents=True)
        jax.config.update("jax_dump_ir_to", str(self.dump))
        self._h0 = self.clock.cache_hits
        self._epoch = time.time()  # the clock the compile spans are on
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        jax.config.update("jax_dump_ir_to", "")
        self.end_epoch = time.time()
        self.record["wall_s"] = time.perf_counter() - self._t0
        self.record["compile_s"] = self.clock.seconds_since(self._epoch)
        self.record["cache_hits"] = self.clock.cache_hits - self._h0
        return False

    def kernel_modules(self) -> list[str]:
        """Names of the compiled programs that carry a Mosaic kernel."""
        return sorted(
            p.name for p in self.dump.glob("*_compile.mlir")
            if "tpu_custom_call" in p.read_text()
        )


def finish_phase(ph: Phase, *, total_s: float, driver_wall_s: float,
                 recovery: dict, compiled: bool) -> None:
    """Fill the phase record, then enforce the path checks.

    ``total_s`` is the entry point's wall, ``driver_wall_s`` its round
    loop's (which ended with the call): set-up is the difference, and run
    is the loop's wall less the compiling done inside the loop.
    """
    import jax

    rec = ph.record
    rec["setup_s"] = total_s - driver_wall_s
    loop_start = ph.end_epoch - driver_wall_s
    rec["run_s"] = driver_wall_s - ph.clock.seconds_since(loop_start)
    stats = [d.memory_stats() for d in jax.local_devices()]
    rec["peak_bytes_in_use"] = [
        None if s is None else s.get("peak_bytes_in_use") for s in stats
    ]
    rec["recovery"] = recovery_counters(recovery)
    modules = ph.kernel_modules()
    rec["kernel_modules"] = modules
    print("one chip run, phase " + json.dumps(rec, default=str), flush=True)
    events = recovery_events(recovery)
    if events:
        raise SmokeFailure(f"{ph.name}: the round loop recovered from faults {events}")
    if compiled and not modules:
        raise SmokeFailure(
            f"{ph.name}: no compiled program contains tpu_custom_call — the "
            "kernels ran interpreted or not at all"
        )


def run_single(graph, engine: str, ref, clock, dump_root, compiled: bool):
    """Phase (a): the single-device fused-kernel engine."""
    from repro.core import betweenness_centrality

    with Phase(f"a/{engine}", clock, dump_root) as ph:
        t0 = time.perf_counter()
        res = betweenness_centrality(
            graph, batch_size=BATCH, heuristics="h0", engine_kind=engine,
            sampling="fixed", sample_k=SAMPLE_K, sample_seed=SAMPLE_SEED,
        )
        total = time.perf_counter() - t0
    raw = res.bc / res.sampling_stats["scale"]
    ph.record["rounds"] = res.rounds_run
    ph.record["max_rel_err"] = check_close(ph.name, raw, ref)
    finish_phase(ph, total_s=total, driver_wall_s=res.wall_s,
                 recovery=res.recovery_stats, compiled=compiled)
    return res


def run_distributed(graph, mesh, ref, clock, dump_root, compiled: bool,
                    *, name: str, overlap: str = "none"):
    """Phase (b) / the 2x2 phase: the blocked-sparse 2-D engine."""
    from repro.core.distributed import distributed_betweenness_centrality

    with Phase(name, clock, dump_root) as ph:
        t0 = time.perf_counter()
        res = distributed_betweenness_centrality(
            graph, mesh, batch_size=BATCH, heuristics="h0",
            engine_kind="pallas_sparse", overlap=overlap,
            sampling="fixed", sample_k=SAMPLE_K, sample_seed=SAMPLE_SEED,
            full_result=True,
        )
        total = time.perf_counter() - t0
    raw = res.bc / res.sampling_stats["scale"]
    ph.record["rounds"] = res.rounds_run
    ph.record["max_rel_err"] = check_close(ph.name, raw, ref)
    finish_phase(ph, total_s=total, driver_wall_s=res.wall_s,
                 recovery=res.recovery_stats, compiled=compiled)
    return res, ph.record


def run_serving_phase(graph, mesh, want_bc, clock, dump_root, compiled: bool):
    """Phase (c): one served generation, checked against phase (b)."""
    import numpy as np

    from repro.launch.serve_bc import run_serving

    with Phase("c/serving", clock, dump_root) as ph:
        with tempfile.TemporaryDirectory() as tmp:
            out = run_serving(
                graph, mesh, ckpt_path=os.path.join(tmp, "bc.npz"),
                batch_size=BATCH, engine="pallas_sparse", sampling="fixed",
                sample_k=SAMPLE_K, sample_seed=SAMPLE_SEED, generations=1,
                queries=QUERIES, top_k=TOP_K,
            )
    (run,) = out["refresh_runs"]
    stats = out["stats"]
    ph.record["queries"] = stats
    ph.record["refresh_s"] = run["wall_s"]
    check_close(ph.name, out["final_bc"], want_bc, rtol=1e-6, atol=1e-6)
    top = np.asarray(out["final_top_k"], np.int64)
    want_top = np.sort(want_bc)[::-1][:TOP_K]
    check_close(f"{ph.name} top-{TOP_K}", np.sort(want_bc[top])[::-1], want_top,
                rtol=1e-6, atol=1e-6)
    if stats["queries"] < QUERIES or stats["hits"] < 1:
        raise SmokeFailure(f"{ph.name}: queries went unanswered ({stats})")
    finish_phase(ph, total_s=run["wall_s"], driver_wall_s=run["driver_wall_s"],
                 recovery=run["recovery"], compiled=compiled)


def check_shards(graph, rec: dict) -> None:
    """Every device of the 2x2 mesh held at least its own adjacency shard."""
    from repro.core.distributed import estimate_device_footprint
    from repro.graphs.partition import partition_2d

    peaks = rec["peak_bytes_in_use"]
    if any(p is None for p in peaks):
        print("shard check: the backend reports no memory stats", flush=True)
        return
    shard = estimate_device_footprint(
        partition_2d(graph, 2, 2), "pallas_sparse", BATCH, overlap="expand+fold"
    )["adjacency_bytes"]
    print(
        f"shard check: per-device adjacency shard {shard} bytes, "
        f"device peaks {peaks}", flush=True,
    )
    if min(peaks) < shard or max(peaks) > 2 * min(peaks):
        raise SmokeFailure(
            f"devices did not each hold their own shard: peaks {peaks}, "
            f"shard {shard} bytes"
        )


def main() -> int:
    args = parse_args(sys.argv[1:])
    if args.rehearse and args.mesh and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print(
            f"chip_smoke: JAX found no TPU (platform {device.platform!r}); "
            "this check runs on the chip", file=sys.stderr,
        )
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.graphs import rmat_graph
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.roofline.model import device_hardware
    from repro.serving import eligible_roots, plan_sampling

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_time_span_listener(clock.on_span)
    jax.monitoring.register_event_listener(clock.on_event)
    n_chips = 4 if args.mesh else 1
    if len(jax.devices()) < n_chips:
        print(f"chip_smoke: needs {n_chips} devices, found {len(jax.devices())}",
              file=sys.stderr)
        return 1
    hw = device_hardware(device)
    scale = REHEARSAL_SCALE if args.rehearse else (SCALE_2X2 if args.mesh else SCALE)
    compiled = not args.rehearse
    print(
        f"one chip run: {device.platform} {device.device_kind!r} x"
        f"{len(jax.devices())} (peaks from {hw.name}); compile cache {cache_dir}",
        flush=True,
    )

    t0 = time.perf_counter()
    graph = rmat_graph(scale, EDGE_FACTOR, seed=GRAPH_SEED)
    graph_s = time.perf_counter() - t0
    plan = plan_sampling(
        eligible_roots(graph), "fixed", sample_k=SAMPLE_K, seed=SAMPLE_SEED
    )
    t0 = time.perf_counter()
    ref = oracle_sum(graph, plan.roots)
    oracle_s = time.perf_counter() - t0
    print(
        f"graph: rmat SCALE {scale} EF {EDGE_FACTOR} seed {GRAPH_SEED}, "
        f"n={graph.n} arcs={graph.num_arcs} ({graph_s:.3f}s); oracle over "
        f"{plan.k} of {plan.num_eligible} roots ({oracle_s:.3f}s)",
        flush=True,
    )

    with tempfile.TemporaryDirectory() as dump_root:
        dump_root = pathlib.Path(dump_root)
        if args.mesh:
            mesh = make_mesh((2, 2), ("data", "model"))
            _, rec = run_distributed(
                graph, mesh, ref, clock, dump_root, compiled,
                name="2x2/pallas_sparse/expand+fold", overlap="expand+fold",
            )
            check_shards(graph, rec)
        else:
            for engine in ("pallas", "pallas_bf16"):
                run_single(graph, engine, ref, clock, dump_root, compiled)
                gc.collect()
            mesh = make_mesh((1, 1), ("data", "model"))
            res_b, _ = run_distributed(
                graph, mesh, ref, clock, dump_root, compiled,
                name="b/1x1/pallas_sparse",
            )
            gc.collect()
            run_serving_phase(graph, mesh, res_b.bc, clock, dump_root, compiled)

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
