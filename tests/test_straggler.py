"""Multi-ledger straggler scheduling (BCDriver straggler="steal"|"redeal").

Three layers of checks:

* pure scheduling functions — ``split_rounds`` / ``redeal_rounds``
  (core/scheduler.py) and the per-replica ledger namespacing of
  ``BCCheckpoint`` (checkpoint/checkpointer.py);
* forced-straggler driver runs on a *fake* two-lane round function (each
  lane runs the real single-device traversal, no mesh needed): BC parity
  with ``brandes_reference`` under steal and redeal, exactly-once across
  speculative duplicates (no double-commit) and across kill-and-resume —
  including a policy change between the crash and the resume;
* real-mesh parity — ``distributed_betweenness_centrality`` with
  ``straggler=`` on a replicated 8-fake-device mesh stays within 1e-6 of
  the oracle under a ring overlap policy (the lockstep schedule the
  re-deal optimizes).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import betweenness_centrality, brandes_reference, engine
from repro.core.driver import (
    BCDriver,
    STRAGGLER_POLICIES,
    normalize_straggler,
    traversal_round,
)
from repro.core.scheduler import build_schedule, redeal_rounds, split_rounds
from repro.checkpoint import BCCheckpoint
from repro.distributed.fault_tolerance import RoundLedger
from repro.graphs import (
    disjoint_union,
    gnp_graph,
    path_graph,
    skewed_depth_graph,
)


# ------------------------------------------------- pure scheduling logic
def test_split_rounds_matches_legacy_block_order():
    # lane r gets rounds r, r+fr, ... — the legacy interleaved deal
    assert split_rounds(7, 2) == [[0, 2, 4, 6], [1, 3, 5]]
    assert split_rounds(6, 3) == [[0, 3], [1, 4], [2, 5]]
    assert split_rounds(5, 2, committed={0, 3}) == [[2, 4], [1]]
    with pytest.raises(ValueError):
        split_rounds(4, 0)


def test_redeal_rounds_packs_similar_costs_together():
    queues = [[0, 2, 4, 6], [1, 3, 5, 7]]  # lane 0 deep (cost 10), lane 1 cheap
    new, moved = redeal_rounds(queues, [10.0, 1.0])
    # costliest-first row-major deal: the first blocks pair lane-0 rounds
    assert new == [[0, 4, 1, 5], [2, 6, 3, 7]]
    assert moved == 4  # half the pool changed lanes
    # exactly-once: the re-deal is a permutation, never a duplication
    assert sorted(r for q in new for r in q) == list(range(8))
    with pytest.raises(ValueError):
        redeal_rounds(queues, [1.0])


def test_straggler_policy_validation():
    assert normalize_straggler(None) == "none"
    assert set(STRAGGLER_POLICIES) == {"none", "steal", "redeal"}
    with pytest.raises(ValueError, match="straggler"):
        normalize_straggler("work-steal")
    with pytest.raises(ValueError, match="straggler"):
        betweenness_centrality(gnp_graph(10, 0.3, seed=1), straggler="steal")
    g = gnp_graph(10, 0.3, seed=1)
    schedule, prep, _, _ = build_schedule(g, batch_size=4)
    with pytest.raises(ValueError, match="ledger"):
        BCDriver(
            lambda s, d: None,
            schedule,
            n=g.n,
            straggler="redeal",
            rounds_per_dispatch=2,
            ledger=RoundLedger(),
        )


# ------------------------------------------- checkpoint ledger namespacing
def test_bc_checkpoint_namespacing_roundtrip(tmp_path):
    ckpt = BCCheckpoint(str(tmp_path / "bc.npz"))
    bc = np.arange(5, dtype=np.float64)
    ckpt.save(bc, {3: 7.0}, [[0, 2], [1]], "fp")
    # legacy load sees the merged union
    bc2, ns, committed = ckpt.load("fp")
    np.testing.assert_array_equal(bc2, bc)
    assert ns == {3: 7.0}
    assert committed == [0, 1, 2]
    # namespaced load keeps per-replica attribution
    _, _, by_lane = ckpt.load_namespaced("fp")
    assert by_lane == [[0, 2], [1]]
    with pytest.raises(ValueError, match="different"):
        ckpt.load_namespaced("other-fp")
    # a flat (single-ledger) save loads as one namespaced lane
    ckpt.save(bc, {}, [4, 1], "fp")
    _, _, by_lane = ckpt.load_namespaced("fp")
    assert by_lane == [[1, 4]]


# ------------------------------------------------ forced-straggler driver
class Crash(RuntimeError):
    pass


def _two_lane_round_fn(graph, crash_after=None):
    """Fake two-replica dispatch: each lane runs the real single-device
    traversal of its round (bc [2, n]; the driver treats the leading dim
    as the replica dim exactly as on a mesh)."""
    adjacency = jnp.asarray(graph.dense_adjacency(np.float32))
    omega = jnp.zeros(graph.n, jnp.float32)
    base = jax.jit(
        lambda s, d: traversal_round(
            engine.make_dense_operator(adjacency), s, d, omega
        )
    )
    calls = {"n": 0}

    def fn(sources, derived):
        calls["n"] += 1
        if crash_after is not None and calls["n"] > crash_after:
            raise Crash
        outs = [base(sources[r], derived[r]) for r in range(sources.shape[0])]
        return tuple(jnp.stack([o[i] for o in outs]) for i in range(4))

    return fn


def _run(graph, schedule, prep, policy, **kw):
    return BCDriver(
        _two_lane_round_fn(graph),
        schedule,
        n=graph.n,
        prep=prep,
        rounds_per_dispatch=2,
        straggler=policy,
        **kw,
    ).run()


@pytest.mark.parametrize("policy", ["steal", "redeal"])
def test_forced_straggler_parity(policy):
    """One lane draws every deep (path) round, the other every shallow
    (complete-graph) round; both policies must reproduce the oracle."""
    g = skewed_depth_graph(4, 8)  # 8 rounds: deep/shallow alternating
    schedule, prep, _, _ = build_schedule(g, batch_size=8)
    assert len(schedule.rounds) == 8
    result = _run(g, schedule, prep, policy, prior_round_s=1e-3)
    np.testing.assert_allclose(result.bc, brandes_reference(g), rtol=1e-6, atol=1e-6)
    assert result.rounds_run == 8
    stats = result.straggler_stats
    assert stats["policy"] == policy
    assert sum(stats["per_replica_rounds"]) == 8
    if policy == "redeal":
        # the EWMA skew (path depth 8 vs clique depth 2) must have fired
        assert stats["redeal_events"] >= 1
        assert stats["rounds_redealt"] > 0


def test_steal_duplicates_are_discarded_not_double_committed():
    """With an odd round count one lane idles at the tail and dispatches a
    speculative duplicate of the straggler's round; BC parity proves the
    loser was masked out before accumulation (a double commit would
    double that round's contribution)."""
    g = disjoint_union(skewed_depth_graph(3, 8), path_graph(8))  # 7 rounds
    schedule, prep, _, _ = build_schedule(g, batch_size=8)
    assert len(schedule.rounds) == 7
    result = _run(g, schedule, prep, "steal")
    np.testing.assert_allclose(result.bc, brandes_reference(g), rtol=1e-6, atol=1e-6)
    stats = result.straggler_stats
    assert stats["duplicates_dispatched"] >= 1
    assert stats["duplicates_discarded"] == stats["duplicates_dispatched"]
    assert result.rounds_run == 7  # duplicates are not extra commits


@pytest.mark.parametrize("resume_policy", ["redeal", "steal", "none"])
def test_straggler_kill_and_resume(tmp_path, resume_policy):
    """Kill mid-run under redeal, resume under any policy: the merged
    per-replica ledgers keep every round exactly-once (a round committed
    by the replica that stole it before the kill is never re-accumulated,
    no matter which lane would execute it after the resume)."""
    g = skewed_depth_graph(4, 8)
    schedule, prep, _, _ = build_schedule(g, batch_size=8)
    assert schedule.derived_per_round == 0  # h0 derives nothing: [fr, 0, 3] blocks
    n_rounds = len(schedule.rounds)
    expected = brandes_reference(g)
    ckpt = BCCheckpoint(str(tmp_path / "bc.npz"))

    def driver(policy, crash_after=None):
        return BCDriver(
            _two_lane_round_fn(g, crash_after=crash_after),
            schedule,
            n=g.n,
            prep=prep,
            rounds_per_dispatch=2,
            straggler=policy,
            checkpoint=ckpt,
            checkpoint_every=1,
        )

    with pytest.raises(Crash):
        driver("redeal", crash_after=2).run()
    assert ckpt.exists()
    _, _, by_lane = ckpt.load_namespaced()
    committed = {rid for lane in by_lane for rid in lane}
    assert 0 < len(committed) < n_rounds
    assert len(by_lane) == 2  # namespaced per replica

    resumed = driver(resume_policy).run()
    assert resumed.rounds_run == n_rounds - len(committed)
    np.testing.assert_allclose(resumed.bc, expected, rtol=1e-6, atol=1e-6)

    # a third run is a no-op that still reproduces the full scores
    third = driver(resume_policy).run()
    assert third.rounds_run == 0
    np.testing.assert_allclose(third.bc, expected, rtol=1e-6, atol=1e-6)


def test_straggler_requires_levels_output():
    g = gnp_graph(12, 0.3, seed=0)
    schedule, prep, _, _ = build_schedule(g, batch_size=4)
    lane_fn = _two_lane_round_fn(g)

    def legacy_fn(sources, derived):  # 3-tuple: no levels signal
        return lane_fn(sources, derived)[:3]

    driver = BCDriver(
        legacy_fn, schedule, n=g.n, prep=prep,
        rounds_per_dispatch=2, straggler="steal",
    )
    with pytest.raises(ValueError, match="levels"):
        driver.run()


# ----------------------------------------------------- real-mesh parity
@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")
@pytest.mark.parametrize("policy", ["steal", "redeal"])
def test_distributed_straggler_matches_oracle(policy):
    """Replicated mesh + ring overlap (loop-bound lockstep) + divergent
    per-replica depths: the exact regime the re-deal schedules for."""
    from repro.core.distributed import distributed_betweenness_centrality
    from repro.launch.mesh import make_mesh

    g = disjoint_union(path_graph(40), gnp_graph(16, 0.3, seed=4))
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g,
        mesh,
        replica_axis="pod",
        batch_size=8,
        overlap="expand",
        straggler=policy,
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-6, atol=1e-6)


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")
def test_distributed_straggler_needs_replicas():
    from repro.core.distributed import distributed_betweenness_centrality
    from repro.launch.mesh import make_mesh

    g = gnp_graph(16, 0.3, seed=0)
    mesh = make_mesh((2, 4), ("data", "model"))
    with pytest.raises(ValueError, match="replica"):
        distributed_betweenness_centrality(g, mesh, straggler="redeal")
