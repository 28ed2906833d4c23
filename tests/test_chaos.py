"""Chaos-harness coverage: every fault class injected and self-healed.

Four layers:

* :class:`FaultPlan` parsing / query semantics (pure, no jax);
* forced-fault driver runs on the fake two-lane round fn (the
  test_straggler.py harness): transient retry + backoff, poison
  quarantine + fallback recompute, replica kill + elastic re-mesh,
  crash + generational resume — BC parity with ``brandes_reference``
  and exactly-once commit counts throughout;
* self-verifying rounds: finite ``flip`` corruption caught by the
  ABFT/claim audits (and, for the audit-evading deep flip, by the
  duplicate vote on steal-duplicated tail rounds); ``stall`` past the
  dispatch deadline tripped by the watchdog on an injectable fake
  clock, escalating re-dispatch → replica loss; detection counters
  surviving kill-and-resume;
* durable-state corruption: torn / garbled :class:`BCCheckpoint`
  generations and autotune cache files must warn and fall back (or
  cold-start), never traceback; a kill mid-save touches only the
  ``.tmp.npz``; ``Checkpointer.close()`` joins its writer thread even
  when a queued write failed;
* real-mesh fault matrix (8 fake host devices): the distributed entry
  point under combined plans stays within 1e-6 of the oracle on 2x4
  and 2x2x2 meshes with recovery telemetry reported.
"""
import json
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import brandes_reference, engine
from repro.core.driver import BCDriver, traversal_round
from repro.core.scheduler import build_schedule
from repro.checkpoint import BCCheckpoint
from repro.checkpoint.checkpointer import Checkpointer
from repro.distributed.chaos import (
    FAULT_KINDS,
    ChaosCostCache,
    ChaosCrash,
    ChaosFS,
    ChaosRoundFn,
    FaultPlan,
)
from repro.distributed.fault_tolerance import (
    ReplicaLostError,
    StragglerPolicy,
    TransientRoundError,
    is_transient_error,
    schedule_fingerprint,
)
from repro.graphs import disjoint_union, gnp_graph, path_graph, skewed_depth_graph


# ------------------------------------------------------------ fault plans
def test_fault_plan_parse_and_queries():
    assert set(FAULT_KINDS) == {
        "transient", "poison", "kill", "crash", "torn", "cache",
        "flip", "stall",
    }
    plan = FaultPlan.parse(
        "seed=7; transient@1x2, poison@3:inf; kill@4:r1; torn@0; "
        "cache@2x2; crash@9"
    )
    assert plan.seed == 7 and len(plan.events) == 6 and bool(plan)
    assert plan.transient_at(1) and plan.transient_at(2)
    assert not plan.transient_at(0) and not plan.transient_at(3)
    assert plan.poison_at(3) == "inf" and plan.poison_at(2) is None
    assert plan.killed_replicas(3) == set()
    # a kill is permanent: count is ignored, loss is loss
    assert plan.killed_replicas(4) == {1} == plan.killed_replicas(99)
    assert plan.crash_at(9) and not plan.crash_at(8)
    assert plan.torn_save(0) and not plan.torn_save(1)
    assert plan.corrupt_cache_put(2) and plan.corrupt_cache_put(3)
    assert not plan.corrupt_cache_put(4)
    # idempotent on FaultPlan / None
    assert FaultPlan.parse(plan) is plan
    assert not FaultPlan.parse(None)
    # repr round-trips through parse
    inner = repr(plan)[len("FaultPlan("):-1]
    again = FaultPlan.parse(inner)
    assert again.events == plan.events and again.seed == plan.seed


def test_fault_plan_flip_and_stall_queries():
    plan = FaultPlan.parse(
        "flip@1; flip@2:r1; flip@3:d0; flip@4:neg; stall@5x2; stall@7:120"
    )
    assert plan.flip_at(0) is None
    assert plan.flip_at(1) == ("scale", 0)  # bare flip: lane 0, sum moves
    assert plan.flip_at(2) == ("scale", 1)
    assert plan.flip_at(3) == ("deep", 0)  # claim recomputed: SDC-style
    assert plan.flip_at(4) == ("neg", 0)
    assert plan.stall_ms(4) is None
    from repro.distributed.chaos import DEFAULT_STALL_MS

    assert plan.stall_ms(5) == plan.stall_ms(6) == DEFAULT_STALL_MS
    assert plan.stall_ms(7) == 120.0
    # repr round-trips through parse with the new kinds present
    inner = repr(plan)[len("FaultPlan("):-1]
    again = FaultPlan.parse(inner)
    assert again.events == plan.events


@pytest.mark.parametrize(
    "spec",
    ["bogus@1", "transient", "transient@-1", "kill@2", "poison@1:huge",
     "transient@1x0", "kill@2:one", "flip@1:x3", "flip@1:rr", "stall@2:fast"],
)
def test_fault_plan_rejects_bad_entries(spec):
    with pytest.raises(ValueError):
        FaultPlan.parse(spec)


def test_straggler_policy_history_is_bounded():
    pol = StragglerPolicy(window=16)
    for i in range(1000):
        pol.observe(float(i))
    assert len(pol.times) == 16
    assert pol.times[0] == 984.0  # oldest observations fell off


# ------------------------------------------------ forced-fault driver runs
@pytest.fixture(scope="module")
def case():
    g = skewed_depth_graph(4, 8)  # 8 source rounds at batch_size=8
    schedule, prep, _, _ = build_schedule(g, batch_size=8)
    assert len(schedule.rounds) == 8
    return g, schedule, prep, brandes_reference(g)


def _two_lane_round_fn(graph, integrity="off"):
    """Fake two-replica dispatch (see tests/test_straggler.py): each lane
    runs the real single-device traversal of its round."""
    adjacency = jnp.asarray(graph.dense_adjacency(np.float32))
    omega = jnp.zeros(graph.n, jnp.float32)
    base = jax.jit(
        lambda s, d: traversal_round(
            engine.make_dense_operator(adjacency), s, d, omega,
            integrity=integrity,
        )
    )

    def fn(sources, derived):
        outs = [base(sources[r], derived[r]) for r in range(sources.shape[0])]
        return tuple(
            jnp.stack([o[i] for o in outs]) for i in range(len(outs[0]))
        )

    return fn


class FakeClock:
    """Deterministic time source for the watchdog: time only advances
    when something sleeps through it (the chaos stall or retry backoff),
    so a stalled dispatch is the *only* thing that can exceed a deadline."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds


def _driver(case, plan=None, sleeper=None, **kw):
    g, schedule, prep, _ = case
    fn = _two_lane_round_fn(g, integrity=kw.get("integrity", "off"))
    round_fn = (
        ChaosRoundFn(fn, FaultPlan.parse(plan), sleeper=sleeper)
        if plan
        else fn
    )
    kw.setdefault("retry_backoff_s", 1e-4)
    return BCDriver(
        round_fn, schedule, n=g.n, prep=prep, rounds_per_dispatch=2,
        sleeper=sleeper, **kw
    )


def test_transient_rounds_are_retried(case):
    result = _driver(case, "transient@1x2").run()
    np.testing.assert_allclose(result.bc, case[3], rtol=1e-6, atol=1e-6)
    rec = result.recovery_stats
    assert rec["transient_errors"] == 2 and rec["retries"] == 2
    assert result.rounds_run == 8


def test_transient_budget_exhausted_raises(case):
    drv = _driver(case, "transient@0x5", max_retries=1)
    with pytest.raises(TransientRoundError):
        drv.run()
    assert drv.recovery["retries"] == 1


@pytest.mark.parametrize(
    "exc,transient",
    [
        (TransientRoundError("injected"), True),
        (jax.errors.JaxRuntimeError("UNAVAILABLE: peer went away"), True),
        (jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM"), False),
        (jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile"), False),
        (ReplicaLostError(1), False),
        (RuntimeError("UNAVAILABLE: not raised by the runtime"), False),
    ],
    ids=["chaos", "unavailable", "oom", "compile", "replica-lost", "foreign"],
)
def test_only_unavailable_runtime_errors_are_transient(exc, transient):
    """Retry only what can succeed on retry: an OOM or a compile error
    fails identically every time, and retrying it would hide the fault."""
    assert is_transient_error(exc) is transient


def test_poison_block_quarantined_and_recovered(case):
    result = _driver(case, "poison@1", numeric_guard=True).run()
    np.testing.assert_allclose(result.bc, case[3], rtol=1e-6, atol=1e-6)
    rec = result.recovery_stats
    assert rec["quarantined_blocks"] == 1 and rec["retries"] == 1
    assert rec["fallback_recomputes"] == 0


def test_persistent_poison_falls_back_to_clean_round_fn(case):
    g, schedule, prep, expected = case
    clean = _two_lane_round_fn(g)
    drv = BCDriver(
        ChaosRoundFn(clean, FaultPlan.parse("poison@1x100")),
        schedule, n=g.n, prep=prep, rounds_per_dispatch=2,
        retry_backoff_s=1e-4, fallback_round_fn=clean,
    )
    result = drv.run()  # numeric guard auto-on: a fallback was supplied
    np.testing.assert_allclose(result.bc, expected, rtol=1e-6, atol=1e-6)
    rec = result.recovery_stats
    # blocks 1..3 each burn the 2-re-dispatch budget then recompute clean
    assert rec["quarantined_blocks"] == 9
    assert rec["fallback_recomputes"] == 3
    assert result.rounds_run == 8


def test_persistent_poison_without_fallback_raises(case):
    drv = _driver(case, "poison@0x10", numeric_guard=True, max_retries=0)
    with pytest.raises(FloatingPointError, match="non-finite"):
        drv.run()


@pytest.mark.parametrize("policy", ["steal", "redeal"])
def test_replica_kill_triggers_remesh_and_parity(case, policy):
    drv = _driver(case, "kill@1:r1", straggler=policy, prior_round_s=1e-3)
    result = drv.run()
    np.testing.assert_allclose(result.bc, case[3], rtol=1e-6, atol=1e-6)
    rec = result.recovery_stats
    assert rec["remesh_events"] == 1 and rec["dead_replicas"] == [1]
    assert result.rounds_run == 8
    # exactly-once: the committed union is every round, no duplicates
    committed = sorted(r for led in drv.ledgers for r in led.state())
    assert committed == list(range(8))


def test_all_replicas_dead_reraises(case):
    drv = _driver(case, "kill@0:r0;kill@0:r1", straggler="steal")
    with pytest.raises(ReplicaLostError):
        drv.run()
    assert drv.recovery["remesh_events"] == 1  # first loss healed, second fatal


# --------------------------------------- self-verifying rounds (integrity)
@pytest.mark.parametrize("mode", ["audit", "checksum"])
@pytest.mark.parametrize("spec", ["flip@1", "flip@1:neg", "flip@1:r1"])
def test_flip_detected_quarantined_and_redispatched(case, mode, spec):
    """A finite silent corruption is invisible to the numeric guard but
    must be caught by the block audit, quarantined and recomputed."""
    result = _driver(case, spec, integrity=mode).run()
    np.testing.assert_allclose(result.bc, case[3], rtol=1e-6, atol=1e-6)
    rec = result.recovery_stats
    integ = rec["integrity"]
    assert integ["mode"] == mode
    assert integ["checksum_failures"] + integ["audit_failures"] >= 1
    assert rec["quarantined_blocks"] >= 1
    assert result.rounds_run == 8  # exactly-once despite the re-dispatch


def test_flip_unnoticed_without_integrity(case):
    """Control: the same corruption with integrity off silently lands in
    the accumulator — this is exactly the gap the audits close."""
    result = _driver(case, "flip@1").run()
    assert not np.allclose(result.bc, case[3], rtol=1e-6, atol=1e-6)
    integ = result.recovery_stats["integrity"]
    assert integ["mode"] == "off"
    assert integ["audit_failures"] == 0  # nothing looked, nothing found


def test_healthy_checksum_run_reports_tiny_residual(case):
    result = _driver(case, integrity="checksum").run()
    np.testing.assert_allclose(result.bc, case[3], rtol=1e-6, atol=1e-6)
    integ = result.recovery_stats["integrity"]
    assert integ["checksum_failures"] == 0 and integ["audit_failures"] == 0
    assert 0.0 <= integ["max_checksum_residual"] < 1e-4


def test_deep_flip_caught_by_duplicate_vote():
    """A 'deep' flip also forges the block's claimed sum, so every block
    audit passes — only comparing the duplicated tail lanes catches it."""
    g = gnp_graph(20, 0.25, seed=5)
    schedule, prep, _, _ = build_schedule(g, batch_size=4)
    assert len(schedule.rounds) == 5  # odd deal: the tail gets duplicated
    expected = brandes_reference(g)
    fn = _two_lane_round_fn(g, integrity="checksum")
    drv = BCDriver(
        ChaosRoundFn(fn, FaultPlan.parse("flip@2:d1")),
        schedule, n=g.n, prep=prep, rounds_per_dispatch=2,
        straggler="steal", prior_round_s=1e-3, retry_backoff_s=1e-4,
        integrity="checksum",
    )
    result = drv.run()
    np.testing.assert_allclose(result.bc, expected, rtol=1e-6, atol=1e-6)
    integ = result.recovery_stats["integrity"]
    assert integ["votes"] >= 2 and integ["vote_mismatches"] >= 1
    assert integ["quarantined_rounds"] >= 1
    assert any(v["matched"] == "owner" for v in integ["vote_verdicts"])
    # the block audits really were blind to it
    assert integ["checksum_failures"] == 0 and integ["audit_failures"] == 0
    committed = sorted(r for led in drv.ledgers for r in led.state())
    assert committed == list(range(5))


# ------------------------------------------------------ dispatch watchdog
def test_watchdog_static_escalates_to_replica_lost(case):
    """Without a replica pool to absorb the loss, a wedged dispatch ends
    the run with ReplicaLostError instead of hanging forever."""
    clk = FakeClock()
    drv = _driver(
        case, "stall@0x3:50", sleeper=clk.sleep,
        clock=clk, dispatch_deadline_s=0.02, max_retries=2,
    )
    with pytest.raises(ReplicaLostError):
        drv.run()
    integ = drv.recovery["integrity"]
    assert integ["watchdog_trips"] == 3
    assert integ["watchdog_redispatches"] == 2
    assert integ["watchdog_escalations"] == 1


def test_watchdog_stall_escalates_into_remesh_and_parity(case):
    """Under a straggler policy the watchdog's escalation is absorbed by
    the elastic re-mesh: the survivor re-deals the rounds, result exact."""
    clk = FakeClock()
    drv = _driver(
        case, "stall@0x3:50", sleeper=clk.sleep,
        clock=clk, dispatch_deadline_s=0.02, max_retries=2,
        straggler="steal", prior_round_s=1e-3, integrity="audit",
    )
    result = drv.run()
    np.testing.assert_allclose(result.bc, case[3], rtol=1e-6, atol=1e-6)
    rec = result.recovery_stats
    integ = rec["integrity"]
    assert integ["watchdog_trips"] == 3
    assert integ["watchdog_escalations"] == 1
    assert rec["remesh_events"] == 1
    assert result.rounds_run == 8
    committed = sorted(r for led in drv.ledgers for r in led.state())
    assert committed == list(range(8))


def test_watchdog_ignores_fast_dispatches(case):
    clk = FakeClock()
    result = _driver(
        case, sleeper=clk.sleep, clock=clk, dispatch_deadline_s=10.0,
        integrity="audit",
    ).run()
    np.testing.assert_allclose(result.bc, case[3], rtol=1e-6, atol=1e-6)
    integ = result.recovery_stats["integrity"]
    assert integ["watchdog_trips"] == 0


def test_integrity_stats_survive_crash_and_resume(tmp_path, case):
    """Detection counters are part of the durable story: after a crash
    the resumed run still reports the pre-crash detections."""
    g, schedule, prep, expected = case
    path = str(tmp_path / "bc.npz")

    def driver(plan, ckpt):
        fn = _two_lane_round_fn(g, integrity="audit")
        round_fn = ChaosRoundFn(fn, FaultPlan.parse(plan)) if plan else fn
        return BCDriver(
            round_fn, schedule, n=g.n, prep=prep, rounds_per_dispatch=2,
            straggler="redeal", checkpoint=ckpt, checkpoint_every=1,
            integrity="audit", retry_backoff_s=1e-4,
        )

    # flip@1 is detected and recomputed (call 2); the crash lands later
    with pytest.raises(ChaosCrash):
        driver("flip@1;crash@4", BCCheckpoint(path)).run()

    resumed = driver(None, BCCheckpoint(path)).run()
    np.testing.assert_allclose(resumed.bc, expected, rtol=1e-6, atol=1e-6)
    rec = resumed.recovery_stats
    assert rec["integrity"]["audit_failures"] == 1  # remembered, not re-hit
    assert rec["quarantined_blocks"] == 1
    assert resumed.rounds_run < 8  # some blocks survived the crash


def test_crash_and_generational_resume(tmp_path, case):
    g, schedule, prep, expected = case
    path = str(tmp_path / "bc.npz")

    def driver(plan, ckpt):
        fn = _two_lane_round_fn(g)
        round_fn = ChaosRoundFn(fn, FaultPlan.parse(plan)) if plan else fn
        return BCDriver(
            round_fn, schedule, n=g.n, prep=prep, rounds_per_dispatch=2,
            straggler="redeal", checkpoint=ckpt, checkpoint_every=1,
        )

    with pytest.raises(ChaosCrash):
        driver("crash@2", BCCheckpoint(path)).run()
    ckpt = BCCheckpoint(path)
    assert ckpt.exists()
    assert (tmp_path / "bc.npz.g1").exists()  # two snapshots rotated

    resumed = driver(None, ckpt).run()
    np.testing.assert_allclose(resumed.bc, expected, rtol=1e-6, atol=1e-6)
    assert resumed.rounds_run == 4  # blocks 0 and 1 survived the crash
    assert resumed.recovery_stats["resumed_generation"] == 0

    third = driver(None, BCCheckpoint(path)).run()
    assert third.rounds_run == 0
    np.testing.assert_allclose(third.bc, expected, rtol=1e-6, atol=1e-6)


# ---------------------------------------------- durable-state corruption
def test_generation_fallback_after_torn_newest(tmp_path, case, caplog):
    g, schedule, prep, _ = case
    fp = schedule_fingerprint(g.n, schedule)
    ckpt = BCCheckpoint(str(tmp_path / "bc.npz"))
    bc1 = np.ones(g.n)
    ckpt.save(bc1, {}, [0], fp)
    ckpt.save(np.full(g.n, 2.0), {}, [0, 1], fp)
    ChaosFS(FaultPlan.parse("seed=3")).tear_file(tmp_path / "bc.npz")

    with caplog.at_level(logging.WARNING, logger="repro.checkpoint.checkpointer"):
        bc, _, committed = ckpt.load(fp)
    assert ckpt.loaded_generation == 1
    np.testing.assert_array_equal(bc, bc1)
    assert committed == [0]
    assert any("falling back" in r.getMessage() for r in caplog.records)

    # the driver reports the fallback generation in its telemetry
    drv = BCDriver(
        _two_lane_round_fn(g), schedule, n=g.n, rounds_per_dispatch=2,
        checkpoint=ckpt,
    )
    assert drv.recovery["resumed_generation"] == 1


def test_all_generations_corrupt_cold_start(tmp_path, case, caplog):
    g, schedule, prep, expected = case
    fp = schedule_fingerprint(g.n, schedule)
    ckpt = BCCheckpoint(str(tmp_path / "bc.npz"))
    ckpt.save(np.ones(g.n), {}, [0], fp)
    ckpt.save(np.ones(g.n), {}, [0, 1], fp)
    fs = ChaosFS(FaultPlan.parse("seed=4"))
    fs.garble_file(tmp_path / "bc.npz")
    fs.garble_file(tmp_path / "bc.npz.g1")

    with caplog.at_level(logging.WARNING, logger="repro.checkpoint.checkpointer"):
        bc, ns, committed = ckpt.load(fp)  # never a traceback
    assert bc is None and ns == {} and committed == []
    assert ckpt.loaded_generation is None
    assert any("cold start" in r.getMessage() for r in caplog.records)

    # a full run from the dead checkpoint recomputes everything, exactly
    result = BCDriver(
        _two_lane_round_fn(g), schedule, n=g.n, prep=prep,
        rounds_per_dispatch=2, checkpoint=ckpt,
    ).run()
    np.testing.assert_allclose(result.bc, expected, rtol=1e-6, atol=1e-6)
    assert result.rounds_run == 8
    assert result.recovery_stats["resumed_generation"] is None


def test_fingerprint_mismatch_on_intact_snapshot_still_raises(tmp_path):
    ckpt = BCCheckpoint(str(tmp_path / "bc.npz"))
    ckpt.save(np.ones(4), {}, [0], "fp-a")
    with pytest.raises(ValueError, match="different"):
        ckpt.load("fp-b")


def test_legacy_snapshot_without_manifest_loads(tmp_path):
    path = tmp_path / "bc.npz"
    np.savez(
        path,
        bc=np.arange(4, dtype=np.float64),
        ns_roots=np.asarray([0], np.int64),
        ns_vals=np.asarray([4.0]),
        committed=np.asarray([0, 2], np.int64),
        fingerprint=np.asarray("legacy-fp"),
    )
    ckpt = BCCheckpoint(str(path))
    bc, ns, committed = ckpt.load("legacy-fp")
    np.testing.assert_array_equal(bc, np.arange(4))
    assert ns == {0: 4.0} and committed == [0, 2]
    assert ckpt.loaded_generation == 0


def test_kill_mid_save_touches_only_the_tmp_file(tmp_path, monkeypatch):
    ckpt = BCCheckpoint(str(tmp_path / "bc.npz"))
    ckpt.save(np.ones(4), {}, [0], "fp")
    before = (tmp_path / "bc.npz").read_bytes()

    real_savez = np.savez

    def dying_savez(path, **arrays):
        real_savez(path, **arrays)
        with open(path, "r+b") as f:  # torn flush, then the kill
            f.truncate(10)
        raise ChaosCrash("killed mid-save")

    monkeypatch.setattr(np, "savez", dying_savez)
    with pytest.raises(ChaosCrash):
        ckpt.save(np.full(4, 2.0), {}, [0, 1], "fp")
    monkeypatch.undo()

    # the committed snapshot and its rotation are untouched; only the
    # temp file carries the torn write
    assert (tmp_path / "bc.npz").read_bytes() == before
    assert not (tmp_path / "bc.npz.g1").exists()
    assert (tmp_path / "bc.npz.tmp.npz").exists()
    bc, _, committed = ckpt.load("fp")
    np.testing.assert_array_equal(bc, np.ones(4))
    assert committed == [0]


def test_checkpointer_close_joins_worker_after_write_error(tmp_path, monkeypatch):
    ck = Checkpointer(str(tmp_path / "ckpt"), async_writes=True)

    def failing_write(*args, **kwargs):
        raise IOError("disk full")

    monkeypatch.setattr(ck, "_write", failing_write)
    ck.save(0, {"w": np.ones(3)})
    with pytest.raises(IOError, match="disk full"):
        ck.close()  # wait() re-raises, but the worker must still stop
    assert not ck._worker.is_alive()


def test_corrupt_autotune_cache_cold_starts_with_warning(tmp_path, caplog):
    from repro.autotune.cache import CACHE_VERSION, CostCache, CostRecord

    path = tmp_path / "autotune_cache.json"
    cache_logger = "repro.autotune.cache"

    path.write_bytes(b"\x00{{{garbage")
    with caplog.at_level(logging.WARNING, logger=cache_logger):
        assert CostCache(path).num_records() == 0
    assert any("unreadable" in r.getMessage() for r in caplog.records)

    caplog.clear()
    path.write_text(json.dumps({"version": 999, "entries": {}}))
    with caplog.at_level(logging.WARNING, logger=cache_logger):
        assert CostCache(path).num_records() == 0
    assert any("version" in r.getMessage() for r in caplog.records)

    caplog.clear()
    path.write_text(json.dumps({
        "version": CACHE_VERSION,
        "entries": {
            "g_good": {"cfg": CostRecord(0.5).to_json()},
            "g_bad": {"cfg": {"nope": 1}},
        },
    }))
    with caplog.at_level(logging.WARNING, logger=cache_logger):
        cache = CostCache(path)
    assert cache.num_records() == 1 and "g_good" in cache.entries
    assert any("malformed" in r.getMessage() for r in caplog.records)


def test_chaos_cost_cache_garbles_the_named_put(tmp_path, caplog):
    from repro.autotune.cache import CostCache, CostRecord

    path = str(tmp_path / "cache.json")
    fs = ChaosFS(FaultPlan.parse("seed=2;cache@1"))
    cache = ChaosCostCache(path, fs)
    assert isinstance(cache, CostCache)  # as_cache() accepts it unchanged
    cache.put("g", "c0", CostRecord(0.1))  # put 0: intact
    cache.put("g", "c1", CostRecord(0.2))  # put 1: garbled after write
    assert fs.cache_puts == 2 and fs.files_corrupted == [path]

    with caplog.at_level(logging.WARNING, logger="repro.autotune.cache"):
        fresh = CostCache(path)  # warm-start empty, never traceback
    assert fresh.num_records() == 0
    assert any("unreadable" in r.getMessage() for r in caplog.records)


def test_chaos_fs_tear_is_seed_deterministic(tmp_path):
    data = bytes(range(256)) * 8
    (tmp_path / "a").write_bytes(data)
    (tmp_path / "b").write_bytes(data)
    ChaosFS(FaultPlan.parse("seed=9")).tear_file(tmp_path / "a")
    ChaosFS(FaultPlan.parse("seed=9")).tear_file(tmp_path / "b")
    a = (tmp_path / "a").read_bytes()
    assert a == (tmp_path / "b").read_bytes()
    assert 0 < len(a) < len(data)


# ------------------------------------------------- real-mesh fault matrix
@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")
def test_chaos_matrix_2x4_mesh():
    """Grid-only mesh (fr=1): transient + poison healed by retry and the
    chaos-supplied clean fallback, parity within 1e-6."""
    from repro.core.distributed import distributed_betweenness_centrality
    from repro.launch.mesh import make_mesh

    g = gnp_graph(24, 0.2, seed=3)
    mesh = make_mesh((2, 4), ("data", "model"))
    result = distributed_betweenness_centrality(
        g, mesh, batch_size=8,
        chaos="seed=5;transient@1x2;poison@3:nan",
        retry_backoff_s=1e-3,
        full_result=True,
    )
    np.testing.assert_allclose(
        result.bc, brandes_reference(g), rtol=1e-6, atol=1e-6
    )
    rec = result.recovery_stats
    assert rec["transient_errors"] == 2
    assert rec["quarantined_blocks"] >= 1
    assert result.rounds_run == len(result.schedule.rounds)  # exactly-once
    assert rec["chaos"]["dispatch_calls"] > len(result.schedule.rounds)


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")
def test_chaos_matrix_2x2x2_mesh_replica_kill():
    """Replicated mesh: a replica kill mid-run re-meshes onto the
    survivor and still matches the oracle, every round exactly once."""
    from repro.core.distributed import distributed_betweenness_centrality
    from repro.launch.mesh import make_mesh

    g = disjoint_union(path_graph(40), gnp_graph(16, 0.3, seed=4))
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    result = distributed_betweenness_centrality(
        g, mesh, replica_axis="pod", batch_size=8, overlap="expand",
        straggler="steal",
        chaos="seed=1;kill@1:r1",
        retry_backoff_s=1e-3,
        full_result=True,
    )
    np.testing.assert_allclose(
        result.bc, brandes_reference(g), rtol=1e-6, atol=1e-6
    )
    rec = result.recovery_stats
    assert rec["remesh_events"] == 1 and rec["dead_replicas"] == [1]
    assert result.rounds_run == len(result.schedule.rounds)  # exactly-once
    assert rec["chaos"]["plan"].startswith("FaultPlan(")


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")
@pytest.mark.parametrize("engine_kind,overlap", [
    ("sparse", "none"), ("pallas", "expand"),
])
def test_flip_matrix_2x4_mesh(engine_kind, overlap):
    """Grid-only mesh: an injected bit-flip-style corruption is detected
    by the checksum/claim audits on every engine x overlap, the block is
    recomputed and the result matches the oracle to 1e-6."""
    from repro.core.distributed import distributed_betweenness_centrality
    from repro.launch.mesh import make_mesh

    g = gnp_graph(24, 0.2, seed=3)
    mesh = make_mesh((2, 4), ("data", "model"))
    result = distributed_betweenness_centrality(
        g, mesh, batch_size=8, engine_kind=engine_kind, overlap=overlap,
        integrity="checksum",
        chaos="seed=5;flip@1",
        retry_backoff_s=1e-3,
        full_result=True,
    )
    np.testing.assert_allclose(
        result.bc, brandes_reference(g), rtol=1e-6, atol=1e-6
    )
    integ = result.recovery_stats["integrity"]
    assert integ["checksum_failures"] + integ["audit_failures"] >= 1
    assert result.recovery_stats["quarantined_blocks"] >= 1
    assert result.rounds_run == len(result.schedule.rounds)  # exactly-once
    assert integ["max_checksum_residual"] < 1e-3  # the ABFT lane is healthy


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")
def test_flip_matrix_2x2x2_mesh_duplicate_vote():
    """Replicated mesh under steal: a deep flip on the duplicated tail
    lane is caught by the duplicate vote and settled by the tie-breaker."""
    from repro.core.distributed import distributed_betweenness_centrality
    from repro.launch.mesh import make_mesh

    g = disjoint_union(path_graph(40), gnp_graph(16, 0.3, seed=4))
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    result = distributed_betweenness_centrality(
        g, mesh, replica_axis="pod", batch_size=8, straggler="steal",
        integrity="checksum",
        chaos="seed=1;flip@3:d1",
        retry_backoff_s=1e-3,
        full_result=True,
    )
    np.testing.assert_allclose(
        result.bc, brandes_reference(g), rtol=1e-6, atol=1e-6
    )
    integ = result.recovery_stats["integrity"]
    assert integ["votes"] >= 1
    assert result.rounds_run == len(result.schedule.rounds)  # exactly-once


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")
def test_stall_matrix_2x2x2_mesh_watchdog_remesh():
    """Replicated mesh: a dispatch stalled past its deadline is tripped,
    re-dispatched, escalated to replica loss and absorbed by the
    re-mesh — the run finishes exact instead of hanging."""
    from repro.core.distributed import distributed_betweenness_centrality
    from repro.launch.mesh import make_mesh

    g = gnp_graph(20, 0.25, seed=5)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    result = distributed_betweenness_centrality(
        g, mesh, replica_axis="pod", batch_size=4, straggler="steal",
        integrity="audit",
        chaos="seed=13;stall@0x3:200",
        dispatch_deadline_s=0.05, max_retries=2, retry_backoff_s=1e-3,
        full_result=True,
    )
    np.testing.assert_allclose(
        result.bc, brandes_reference(g), rtol=1e-6, atol=1e-6
    )
    rec = result.recovery_stats
    integ = rec["integrity"]
    assert integ["watchdog_trips"] >= 3
    assert integ["watchdog_escalations"] >= 1
    assert rec["remesh_events"] >= 1
    assert result.rounds_run == len(result.schedule.rounds)  # exactly-once


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")
def test_checksum_rejects_split_backward_payload():
    from repro.core.distributed import make_distributed_round_fn
    from repro.graphs.partition import partition_2d
    from repro.launch.mesh import make_mesh

    g = gnp_graph(16, 0.3, seed=0)
    mesh = make_mesh((2, 4), ("data", "model"))
    part = partition_2d(g, 2, 4)
    with pytest.raises(ValueError, match="checksum lane"):
        make_distributed_round_fn(
            part, mesh, fuse_backward_payload=False, integrity="checksum"
        )
