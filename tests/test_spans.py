"""The host span recorder (core/spans.py) and the spans of the 2-D entry
point and the driver's round loop: nesting, runs, the memory bound, the
clock it shares with the profiler, and ``wall_s``/``block_times`` read
from it."""
import glob
import sys
import threading

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import brandes_reference, engine, spans
from repro.core.distributed import distributed_betweenness_centrality
from repro.core.driver import BCDriver, traversal_round
from repro.core.scheduler import build_schedule
from repro.graphs import gnp_graph, rmat_graph

SETUP = ["bc.setup.schedule", "bc.setup.partition", "bc.setup.layout", "bc.setup.transfer"]
BLOCK = ["bc.driver.block", "bc.driver.dispatch", "bc.driver.drain", "bc.driver.collect",
         "bc.driver.stop_rule"]


def test_nesting_parents_and_run_ids():
    with spans.span("root") as root:
        with spans.span("a", k=1) as a:
            with spans.span("b"):
                pass
        with spans.span("c") as c:
            assert root.finished is None  # the run is still open
    run = spans.last_run()
    assert [s.name for s in run] == ["root", "a", "b", "c"]
    assert [s.parent for s in run] == [None, 0, 1, 0]
    assert {s.run for s in run} == {root.run}
    assert a.attrs == {"k": 1}
    assert all(s.start_ns <= s.end_ns for s in run)
    assert run[0].start_ns <= run[1].start_ns <= run[2].end_ns <= run[1].end_ns
    assert root.finished is run and a.finished is None and c.finished is None
    with spans.span("next", k=1) as nxt:
        nxt.annotate(width=8)
    assert nxt.run > root.run and spans.last_run()[0] is nxt
    assert nxt.attrs == {"k": 1, "width": 8}


def test_only_the_newest_runs_are_kept():
    roots = []
    for i in range(spans.KEEP_RUNS + 3):
        with spans.span("root", i=i) as r:
            with spans.span("child"):
                pass
        roots.append(r)
    assert spans.last_run()[0] is roots[-1]
    assert list(spans._finished) == [r.finished for r in roots[-spans.KEEP_RUNS:]]


def test_threads_record_runs_of_their_own():
    roots, errors = [], []

    def work(t):
        try:
            for i in range(200):
                with spans.span("root", t=t, i=i) as r:
                    with spans.span("child", t=t):
                        pass
                roots.append(r)
        except Exception as e:  # surfaced below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(th.is_alive() for th in threads)
    assert len(roots) == 8 * 200 and len({r.run for r in roots}) == len(roots)
    for r in roots:
        run = r.finished
        assert run[0] is r and [s.name for s in run] == ["root", "child"]
        assert run[1].run == r.run and run[1].attrs["t"] == r.attrs["t"]
    kept = list(spans._finished)
    assert len(kept) == spans.KEEP_RUNS and all(run[0] in roots for run in kept)


def test_attach_run_gives_a_root_result_its_run():
    class Out:
        spans = None

    def call():
        out = Out()
        with spans.span("outer") as s:
            pass
        spans.attach_run(s, out)
        return out

    out = call()
    assert [s.name for s in out.spans] == ["outer"]
    with spans.span("caller"):
        nested = call()
    assert nested.spans is None  # not a root: the caller's run holds it


def _mesh():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _entry(stop_after):
    seen = []

    def rule(bc, blocks_done):
        seen.append(blocks_done)
        return blocks_done >= stop_after

    result = distributed_betweenness_centrality(
        rmat_graph(6, 8, seed=1), _mesh(), batch_size=8, engine_kind="sparse",
        sampling="fixed", sample_frac=1.0, stop_rule=rule, full_result=True,
    )
    return result, seen


def test_entry_records_its_spans_in_order():
    result, seen = _entry(stop_after=3)
    run = result.spans
    assert run is spans.last_run() and result.stopped_early and seen == [1, 2, 3]
    names = [s.name for s in run]
    assert names == ["bc.entry"] + SETUP + ["bc.driver.run"] + BLOCK * 3 + ["bc.driver.collect"]
    assert run[0].parent is None and len({s.run for s in run}) == 1
    driver = names.index("bc.driver.run")
    assert [s.parent for s in run[1:driver + 1]] == [0] * (driver)
    blocks = [s for s in run if s.name == "bc.driver.block"]
    assert [b.attrs["block"] for b in blocks] == [1, 2, 3]
    for b in blocks:
        i = run.index(b)
        children = run[i + 1:i + len(BLOCK)]
        assert all(c.parent == i for c in children)
        assert all(c.attrs.get("block", b.attrs["block"]) == b.attrs["block"] for c in children)
        assert b.start_ns <= children[0].start_ns and children[-1].end_ns <= b.end_ns
    assert run[names.index("bc.setup.transfer")].attrs["bytes"] > 0
    # h0 claims no 2-degree vertex: the backward state is the batch wide
    assert run[names.index("bc.setup.schedule")].attrs == {"derived_per_round": 0, "width": 8}
    # the result's timings are the spans' durations
    assert result.wall_s == run[driver].seconds
    assert result.block_times == [b.seconds for b in blocks]


def test_profile_and_straggler_block_times_are_their_spans():
    g = gnp_graph(12, 0.3, seed=2)
    schedule, prep, _, _ = build_schedule(g, batch_size=4)
    op = engine.make_dense_operator(jnp.asarray(g.dense_adjacency(np.float32)))
    omega = jnp.zeros(g.n, jnp.float32)
    base = jax.jit(lambda s, d: traversal_round(op, s, d, omega))

    def lanes(sources, derived):
        outs = [base(sources[r], derived[r]) for r in range(sources.shape[0])]
        return tuple(jnp.stack([o[i] for o in outs]) for i in range(4))

    for kw in ({"profile": True}, {"rounds_per_dispatch": 2, "straggler": "steal"}):
        result = BCDriver(lanes, schedule, n=g.n, prep=prep, **kw).run()
        run = result.spans
        assert run[0].name == "bc.driver.run" and result.wall_s == run[0].seconds
        blocks = [s for s in run if s.name == "bc.driver.block"]
        assert result.block_times == [b.seconds for b in blocks] and blocks
        assert [b.attrs["block"] for b in blocks] == list(range(1, len(blocks) + 1))


def test_a_lost_dispatch_keeps_a_block_number_of_its_own():
    from repro.distributed.fault_tolerance import ReplicaLostError

    g = gnp_graph(12, 0.3, seed=2)
    schedule, prep, _, _ = build_schedule(g, batch_size=2)
    op = engine.make_dense_operator(jnp.asarray(g.dense_adjacency(np.float32)))
    omega = jnp.zeros(g.n, jnp.float32)
    base = jax.jit(lambda s, d: traversal_round(op, s, d, omega))
    calls = []

    def lanes(sources, derived):
        calls.append(len(calls) + 1)
        if len(calls) == 2:
            raise ReplicaLostError(1)
        outs = [base(sources[r], derived[r]) for r in range(sources.shape[0])]
        return tuple(jnp.stack([o[i] for o in outs]) for i in range(4))

    result = BCDriver(lanes, schedule, n=g.n, prep=prep, rounds_per_dispatch=2,
                      straggler="steal").run()
    run = result.spans
    blocks = [s for s in run if s.name == "bc.driver.block"]
    numbers = [b.attrs["block"] for b in blocks]
    assert numbers == list(range(1, len(calls) + 1)) and len(blocks) >= 3
    drained = {s.attrs["block"] for s in run if s.name == "bc.driver.drain"}
    assert drained == set(numbers) - {2}  # the lost dispatch has no drain
    assert result.block_times == [b.seconds for b in blocks if b.attrs["block"] in drained]
    np.testing.assert_allclose(result.bc, brandes_reference(g), rtol=1e-5, atol=1e-5)


def test_spans_share_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData

    _entry(stop_after=2)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        result, _ = _entry(stop_after=2)
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    data = ProfileData.from_file(path)
    start = None
    events = []
    schedule_stats = []
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bc."):
                    events.append((ev.name, dict(ev.stats).get("block"), ev.start_ns))
                if ev.name == "bc.setup.schedule":
                    schedule_stats.append(dict(ev.stats))
    assert start is not None
    # attributes annotated after the span opened reach the trace event
    (stats,) = schedule_stats
    assert int(stats["derived_per_round"]) == 0 and int(stats["width"]) == 8
    events.sort(key=lambda e: e[2])
    recorded = [(s.name, s.attrs.get("block"), s.start_ns) for s in result.spans]
    assert [e[:2] for e in events] == [r[:2] for r in recorded]
    worst = max(abs(e[2] + start - r[2]) for e, r in zip(events, recorded))
    assert worst < 1_000_000, f"{worst} ns apart"
