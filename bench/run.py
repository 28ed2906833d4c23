"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a graph configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``).  The run
builds the configuration's graph on the host, hands it to the program's
2-D entry point ``distributed_betweenness_centrality`` as a stream of
roots drawn from ``--seed``, and times a window of that stream through the
driver's stop rule (``bench/window.py``): the first blocks compile and warm
up and count as set-up, the window then runs for ``--seconds``.  Once the
window has closed and the program's state is freed, a span of the
window's blocks is checked against the plain reference
(``bench/check.py``, ``bench/reference.py``).

``--trace 0`` prints the cell's end-to-end metrics (``mteps``,
``setup_s``); ``--trace 1`` profiles the start of the window and prints
its per-layer metrics, each read by ``bench/metrics/<name>.py``.  The last
line of standard output is one JSON object; the last lines of standard
error are the numbers compared, each beside its limit.  Without a TPU, or
with fewer chips than the cell asks for, the run prints no result and
exits with 2.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cells, check, faults, graphs, trace as tr, window  # noqa: E402
from bench.compile_clock import CompileClock  # noqa: E402

#: the persistent compile cache: JAX_COMPILATION_CACHE_DIR when the
#: environment sets it, else this fixed directory of the checkout
CACHE_DIR = ROOT / ".jax-cache"


#: host clock marks of the start-up before a cell's set-up begins
#: (``import jax``, the TPU runtime's start in ``jax.devices()``)
MARKS: dict[str, float] = {}


class NoChip(RuntimeError):
    """JAX found no TPU, an unknown TPU, or fewer chips than the cell needs."""


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the reduced trace record (JSON) to this file; "
                         "it records the trace under bench/tests/data that the tests read")
    return ap.parse_args(argv)


def load_peaks() -> dict:
    return json.loads((ROOT / "bench" / "peaks.json").read_text())


def require_chips(chips: int, peaks: dict):
    """The devices a cell runs on; raises :class:`NoChip` otherwise."""
    MARKS.setdefault("imports", time.perf_counter())
    import jax

    MARKS.setdefault("jax", time.perf_counter())
    devices = jax.devices()
    MARKS.setdefault("devices", time.perf_counter())
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r}); "
                     "this benchmark runs on the chip only")
    if kind not in peaks["devices"]:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    return path


def log(msg: str) -> None:
    print(msg, flush=True)


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Tracer:
    """Profiles the first ``seconds`` of the window (``--trace 1``)."""

    def __init__(self, seconds: float, directory: str):
        self.seconds = seconds
        self.directory = directory
        self.active = False
        self._span = None
        self._t0 = None

    def start(self, now: float) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
        self._span.__enter__()
        self._t0 = now
        self.active = True

    def on_block(self, blocks_done: int, now: float) -> None:
        if self.active and now - self._t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.active:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False


def build_mesh(devices, shape):
    import jax
    import numpy as np

    axes = ("data", "model")
    return jax.sharding.Mesh(
        np.asarray(devices).reshape(tuple(shape)), axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def run_cell(bench: cells.Benchmark, cell: cells.Cell, *, seed: int, seconds: float,
             trace: bool, devices, peaks: dict, keep_trace: str | None = None,
             mode: str = "program", compile_cache: bool = True) -> dict:
    """One run of ``cell``: set-up, window, check, metrics.  Returns the
    result object that :func:`main` prints last.

    ``mode`` other than ``"program"`` puts the control or a planted fault
    under the timed path (``bench/faults.py``); only ``bench/control.py``
    and the tests do so.
    """
    import jax
    import numpy as np

    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.core import distributed
    from repro.graphs import Graph

    cfg, traffic = cell.config, cell.traffic
    cache_dir = enable_compile_cache() if compile_cache else "off"
    clock = CompileClock()
    jax.monitoring.register_event_time_span_listener(clock.on_span)
    jax.monitoring.register_event_listener(clock.on_event)
    epoch0 = time.time()
    t_start = time.perf_counter()
    dev0 = devices[0]
    log(f"bench: {cell.name} on {dev0.platform} {dev0.device_kind!r} x{len(devices)} "
        f"(JAX sees {len(jax.devices())}); seed {seed}; compile cache {cache_dir}")

    n, edges = graphs.build_edges(cfg["graph"])
    row_ptr, col = graphs.csr(n, edges)
    degree = np.diff(row_ptr)
    eligible = degree >= 1
    num_edges = int(col.size // 2)
    graph = Graph.from_edges(n, edges)
    t_graph = time.perf_counter()
    log(f"graph: {cfg['name']} n={n} edges={num_edges} eligible roots={int(eligible.sum())}")

    tracer = None
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        tracer = Tracer(float(traffic["trace_seconds"]), trace_dir)
    opened = {}

    def on_open(now):
        opened["epoch"] = time.time()
        if tracer is not None:
            tracer.start(now)

    win = window.StreamWindow(
        int(traffic["warmup_blocks"]), seconds,
        on_open=on_open,
        on_block=tracer.on_block if tracer is not None else None,
        annotate=jax.profiler.TraceAnnotation if trace else None,
    )
    mesh = build_mesh(devices, cfg["mesh"])
    entry_span = jax.profiler.TraceAnnotation("bench.entry") if trace else contextlib.nullcontext()
    try:
        with faults.installed(mode, row_ptr=row_ptr, col=col, device=dev0), entry_span:
            result = distributed.distributed_betweenness_centrality(
                graph, mesh,
                batch_size=int(traffic["batch_size"]),
                heuristics=traffic["heuristics"],
                engine_kind=cfg["engine"],
                overlap=cfg["overlap"],
                sampling=traffic["sampling"],
                sample_frac=float(traffic["sample_frac"]),
                sample_seed=seed,
                stop_rule=win,
                full_result=True,
            )
    finally:
        if tracer is not None:
            tracer.stop()
    epoch_end = time.time()
    peak = memory_peak(devices)

    roots_per_block = [
        check.block_roots(result.schedule, k).size for k in range(1, len(win.done_at) + 1)
    ]
    acct = window.account(win, roots_per_block)
    setup_s = win.t_open - T_PROCESS
    mteps = acct.roots * num_edges / acct.seconds / 1e6
    compile_setup_s = clock.seconds_between(epoch0, opened["epoch"])
    compiles_in_window = clock.count_between(opened["epoch"], epoch_end)
    log(f"window: blocks {acct.first_block}..{acct.last_block} ({acct.blocks}), "
        f"{acct.roots} roots in {acct.seconds!r} s; solve finished {acct.solve_finished}")
    if {"imports", "jax", "devices"} <= MARKS.keys():
        log(f"start-up parts: Python and bench imports {MARKS['imports'] - T_PROCESS!r} s, "
            f"import jax {MARKS['jax'] - MARKS['imports']!r} s, TPU runtime start "
            f"(jax.devices) {MARKS['devices'] - MARKS['jax']!r} s")
    log(f"set-up parts: process start to run {t_start - T_PROCESS!r} s, graph "
        f"{t_graph - t_start!r} s, entry to window {win.t_open - t_graph!r} s "
        f"(partition, layout, transfer, compile, {win.warmup_blocks} warm-up blocks "
        f"ending at {[b - t_graph for b in win.done_at[:win.warmup_blocks]]!r} s)")
    log(f"set-up: {setup_s!r} s, of it compiling {compile_setup_s!r} s; persistent-cache "
        f"hits {clock.cache_hits}; compiles inside the window {compiles_in_window}")
    log(f"memory: peak {peak} bytes on the fullest of {len(devices)} chips")
    recovery = {k: v for k, v in (result.recovery_stats or {}).items()
                if k != "integrity" and v}
    if recovery:
        log(f"recovery events: {recovery}")

    # the check runs after the window, once the program's state is freed
    record = None
    if trace:
        record = tr.export(trace_dir)
        if keep_trace:
            pathlib.Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
            pathlib.Path(keep_trace).write_text(json.dumps(record))
    gc.collect()
    t_ref = time.perf_counter()
    from bench.reference import Reference

    ref = Reference(row_ptr, col, batch=int(cfg["reference_batch"]), device=dev0)
    res = check.compare(
        win=win, acct=acct, result=result, eligible=eligible,
        batch_size=int(traffic["batch_size"]), reference=ref,
        limits=cfg["check"], seed=seed,
    )
    ref_s = time.perf_counter() - t_ref
    log(f"check: blocks {res.span[0]}..{res.span[1]} ({res.span_roots} roots) against "
        f"the reference in {ref_s!r} s; levels per reference batch {res.levels}")

    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": peak,
    }
    # what a per-layer metric reader (bench/metrics/<name>.py) is given
    ctx = {
        "config": cfg, "peaks": peaks["devices"].get(dev0.device_kind),
        "n": n, "col": col, "batch_size": int(traffic["batch_size"]),
        "compile_setup_s": compile_setup_s,
        "record": record, "summary": tr.summarize(record) if record else None,
    }
    out = {"correct": res.correct, "attempted": acct.roots,
           "failed": 0 if res.correct else res.span_roots}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = bench.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        summ = ctx["summary"]
        busy = [c["busy_ns"] for c in summ["chips"].values()]
        device["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
        device["window_s"] = summ["window_ns"] / 1e9
        out["metrics"] = metrics
        out["breakdown"] = {"device_ops": tr.top_ops(record), "idle_gaps": tr.idle_gaps(record)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"mteps": mteps, "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = device
    out["check"] = {k: {"value": v, "limit": lim} for k, (v, lim) in res.numbers.items()}
    out["_lines"] = res.lines()
    return out


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    bench = cells.load_benchmark(ROOT)
    cell = bench.cell(args.workload)
    peaks = load_peaks()
    try:
        devices = require_chips(cell.chips, peaks)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = run_cell(bench, cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices, peaks=peaks,
                   keep_trace=args.keep_trace)
    lines = out.pop("_lines")
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
