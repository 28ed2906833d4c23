"""Measured-cost autotuning: cache, planner, seams, eccentricity deals.

Five layers of checks:

* the persistent :class:`CostCache` — roundtrip, atomic persistence,
  corrupt-file tolerance, hit/miss/store accounting;
* the staged planner (:func:`plan_autotune`) on an injected fake bench —
  measure-once semantics (a second plan over the same cache re-measures
  nothing), mode contracts ("off" never consults, "cache" never
  measures), tile/hybrid/overlap stage resolution;
* the four choice seams, each demonstrably preferring a measured cost
  over its roofline estimate: ``cell_kernel_choice(measured=)``,
  ``auto_overlap_policy(measured=)``,
  ``prior_round_seconds(measured_level_s=)``, and the BCSR tile pick;
* scheduler additions — ``validate_batch_size`` (both entrypoints),
  sampled eccentricities, the cost-packed :func:`split_rounds` deal, and
  eccentricity-ordered schedules cutting total traversal levels on the
  depth-skewed graph;
* end-to-end on 8 fake devices — depth-divergent rounds stay at oracle
  parity across every distributed engine × overlap policy, and
  ``distributed_betweenness_centrality(autotune=...)`` round-trips
  measure → cache-hit against a persisted file.
"""
import json
import logging
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.autotune import (
    AUTOTUNE_MODES,
    Candidate,
    CostCache,
    CostRecord,
    config_key,
    graph_key,
    graph_key_for,
    measure_walls,
    normalize_autotune,
    plan_autotune,
    sample_batch,
)
from repro.core import betweenness_centrality, brandes_reference, engine
from repro.core.distributed import (
    DIST_ENGINE_KINDS,
    PRIOR_LEVELS,
    distributed_betweenness_centrality,
    prior_round_seconds,
)
from repro.core.driver import BCDriver, traversal_round
from repro.core.operators import OVERLAP_POLICIES
from repro.core.scheduler import (
    ROOT_ORDERS,
    bfs_depths,
    build_schedule,
    estimate_eccentricities,
    split_rounds,
    validate_batch_size,
)
from repro.graphs import (
    complete_graph,
    disjoint_union,
    gnp_graph,
    path_graph,
    skewed_depth_graph,
)
from repro.graphs.partition import partition_2d
from repro.roofline.model import auto_overlap_policy, cell_kernel_choice

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host devices"
)


# ---------------------------------------------------------- cost cache
def test_cache_roundtrip_and_persistence(tmp_path):
    path = tmp_path / "tune.json"
    cache = CostCache(path)
    gkey = graph_key(32, 100, R=2, C=4)
    ckey = config_key("sparse", "none", 16)
    assert cache.get(gkey, ckey) is None
    assert cache.misses == 1
    rec = CostRecord(level_s=0.25, levels=4, walls=(2.0, 2.1))
    cache.put(gkey, ckey, rec)
    assert cache.stores == 1 and path.exists()
    assert cache.get(gkey, ckey) == rec
    assert cache.hits == 1

    # a fresh instance loads the persisted record
    cache2 = CostCache(path)
    assert cache2.num_records() == 1
    assert cache2.get(gkey, ckey) == rec
    # a different graph key is a miss — measurements never cross graphs
    assert cache2.get(graph_key(64, 100, R=2, C=4), ckey) is None
    stats = cache2.stats()
    assert stats["records"] == 1 and stats["hits"] == 1 and stats["misses"] == 1

    # the persisted file is valid versioned JSON
    obj = json.loads(path.read_text())
    assert obj["version"] == 1 and gkey in obj["entries"]


def test_cache_tolerates_corrupt_and_foreign_files(tmp_path):
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{not json")
    cache = CostCache(garbage)
    assert cache.num_records() == 0

    wrong_version = tmp_path / "old.json"
    wrong_version.write_text(json.dumps({"version": 999, "entries": {"g": {}}}))
    assert CostCache(wrong_version).num_records() == 0

    # a corrupt-at-load cache is still writable (fresh start)
    cache.put("g", "c", CostRecord(level_s=1.0))
    assert CostCache(garbage).num_records() == 1

    # in-memory mode: no path, nothing on disk
    mem = CostCache(None)
    mem.put("g", "c", CostRecord(level_s=1.0))
    assert mem.num_records() == 1 and mem.stats()["path"] is None


def test_key_schemas(monkeypatch):
    assert graph_key(32, 100, R=2, C=4, fr=2, nnz_tiles=7, degree_skew=3.14) == (
        "n32_m100_r2x4x2_t7_k3.1@cpu"
    )
    # the key names the measuring device: a wall measured on the CPU
    # (interpreted kernels) is never served to a chip run
    cpu_key = graph_key(32, 100, R=2, C=4)
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    assert graph_key(32, 100, R=2, C=4) == "n32_m100_r2x4x1_t0_k1.0@TPU v5 lite"
    cache = CostCache(None)
    cache.put(cpu_key, "sparse|none|b4|t-", CostRecord(level_s=1.0))
    assert cache.get(graph_key(32, 100, R=2, C=4), "sparse|none|b4|t-") is None
    monkeypatch.undo()
    assert config_key("pallas_sparse", "expand", 16, (8, 8)) == (
        "pallas_sparse|expand|b16|t8x8"
    )
    assert config_key("sparse", "none", 4) == "sparse|none|b4|t-"

    g = gnp_graph(30, 0.2, seed=1)
    part = partition_2d(g, 2, 2)
    key = graph_key_for(part, g, fr=2)
    assert key.startswith(f"n{part.n}_m{int(part.arc_counts.sum())}_r2x2x2_")
    # same configuration -> same key (measure-once across runs)
    assert key == graph_key_for(partition_2d(g, 2, 2), g, fr=2)


def test_normalize_autotune():
    assert normalize_autotune(None) == "off"
    for mode in AUTOTUNE_MODES:
        assert normalize_autotune(mode) == mode
    with pytest.raises(ValueError, match="autotune"):
        normalize_autotune("bogus")
    # the distributed entrypoint validates before touching the mesh
    with pytest.raises(ValueError, match="autotune"):
        distributed_betweenness_centrality(
            gnp_graph(6, 0.5, seed=0), None, autotune="bogus"
        )


def test_measure_walls_fake_clock():
    ticks = iter(float(t) for t in range(100))
    runs = []
    walls = measure_walls(
        lambda: runs.append(1), clock=lambda: next(ticks), warmup=1, iters=3
    )
    assert len(runs) == 4  # 1 warmup + 3 timed
    assert walls == [1.0, 1.0, 1.0]  # clock pairs straddle each run


# ------------------------------------------------- planner (fake bench)
def _plan_fixture():
    g = gnp_graph(64, 0.15, seed=5)
    part = partition_2d(g, 2, 2)  # chunk 16 -> tile menu [(16,16), (8,8)]
    assert len(part.tile_candidates()) >= 2
    return g, part


def test_plan_off_mode_consults_nothing():
    g, part = _plan_fixture()

    def bench(cand):  # pragma: no cover - must never run
        raise AssertionError("off mode measured a candidate")

    cache = CostCache(None)
    plan = plan_autotune(
        part, engine_kind="pallas_sparse", overlap="auto", batch_size=16,
        mode="off", cache=cache, graph=g, bench=bench,
    )
    assert plan.mode == "off" and plan.tile is None
    assert plan.hits == plan.misses == plan.measured == 0
    assert cache.hits == cache.misses == 0


def test_plan_cache_mode_never_measures_and_rooflines_tile():
    g, part = _plan_fixture()

    def bench(cand):  # pragma: no cover - must never run
        raise AssertionError("cache mode measured a candidate")

    plan = plan_autotune(
        part, engine_kind="pallas_sparse", overlap="auto", batch_size=16,
        mode="cache", cache=CostCache(None), graph=g, bench=bench,
    )
    assert plan.measured == 0 and plan.misses > 0
    # empty cache -> no measured costs anywhere; tile falls back to roofline
    assert plan.tile_source == "roofline"
    assert plan.tile in part.tile_candidates()
    assert plan.overlap_level_s == {} and plan.cell_costs is None
    assert plan.level_s_for("none") is None


def test_tile_pick_prefers_measured_over_roofline():
    g, part = _plan_fixture()
    cands = part.tile_candidates()
    roof = plan_autotune(
        part, engine_kind="pallas_sparse", overlap="none", batch_size=16,
        mode="cache", cache=CostCache(None), graph=g,
    )
    assert roof.tile_source == "roofline"
    # make the tile the roofline did NOT pick measure cheapest
    other = next(t for t in cands if t != roof.tile)

    def bench(cand):
        return CostRecord(level_s=1.0 if cand.tile == other else 9.0, levels=4)

    meas = plan_autotune(
        part, engine_kind="pallas_sparse", overlap="none", batch_size=16,
        mode="measure", cache=CostCache(None), graph=g, bench=bench,
    )
    assert meas.tile_source == "measured"
    assert meas.tile == other and meas.tile != roof.tile
    # the stage-3 overlap consult reuses the stage-1 record (same key)
    assert meas.level_s_for("none") == 1.0
    assert meas.hits >= 1

    # an explicit tile is never second-guessed
    explicit = plan_autotune(
        part, engine_kind="pallas_sparse", overlap="none", batch_size=16,
        tile=cands[0], mode="measure", cache=CostCache(None), graph=g,
        bench=bench,
    )
    assert explicit.tile == cands[0] and explicit.tile_source == "explicit"


def test_plan_measure_once_across_runs(tmp_path):
    g, part = _plan_fixture()
    path = tmp_path / "tune.json"

    def make_bench(calls):
        def bench(cand):
            calls.append(cand.key())
            cost = {"pallas": 3.0, "pallas_sparse": 1.0}.get(cand.engine_kind, 2.0)
            cost += {"none": 0.3, "expand": 0.2, "expand+fold": 0.1}[cand.overlap]
            return CostRecord(level_s=cost, levels=4, walls=(cost,))

        return bench

    kwargs = dict(
        engine_kind="pallas_hybrid", overlap="auto", batch_size=16,
        mode="measure", graph=g,
    )
    cold_calls: list[str] = []
    plan1 = plan_autotune(
        part, cache=CostCache(path), bench=make_bench(cold_calls), **kwargs
    )
    assert plan1.measured == len(cold_calls) > 0
    assert len(set(cold_calls)) == len(cold_calls)  # no key measured twice
    assert plan1.tile is not None and plan1.tile_source == "measured"
    assert plan1.cell_costs is not None
    assert set(plan1.overlap_level_s) == set(OVERLAP_POLICIES)

    # a second planner over the persisted file re-measures NOTHING and
    # resolves identically
    warm_calls: list[str] = []
    plan2 = plan_autotune(
        part, cache=CostCache(path), bench=make_bench(warm_calls), **kwargs
    )
    assert warm_calls == [] and plan2.measured == 0
    assert plan2.hits == plan1.hits + plan1.measured  # every consult hit
    assert plan2.tile == plan1.tile
    assert plan2.cell_costs == plan1.cell_costs
    assert plan2.overlap_level_s == plan1.overlap_level_s
    report = plan2.report()
    assert report["mode"] == "measure" and report["measured"] == 0


# ------------------------------------------------------ the four seams
def test_seam_cell_kernel_choice_prefers_measured():
    stored = np.array([[10.0, 0.0], [5.0, 10.0]])
    kw = dict(R=2, C=2, chunk=16, bm=8, bk=8)
    roofline = cell_kernel_choice(stored, **kw)
    # measured calibration overrides the bytes model entirely: a cheap
    # BCSR wall keeps every cell sparse, a cheap dense wall flips every
    # populated cell dense
    all_sparse = cell_kernel_choice(stored, measured=(1.0, 1e-3), **kw)
    assert not all_sparse.any()
    all_dense = cell_kernel_choice(stored, measured=(1e-6, 10.0), **kw)
    assert all_dense[stored > 0].all()
    # at least one extreme disagrees with the bytes model on this grid —
    # the measured pair, not the model, decided
    assert (all_sparse != roofline).any() or (all_dense != roofline).any()
    # threshold still applies on the measured scale
    forced_sparse = cell_kernel_choice(stored, measured=(1e-6, 10.0),
                                       R=2, C=2, chunk=16, bm=8, bk=8,
                                       threshold=1e12)
    assert not forced_sparse.any()


def test_seam_auto_overlap_policy_prefers_measured():
    model_pick, estimates = auto_overlap_policy(1e-3, 5e-4, 5e-4, 2, 4)
    assert model_pick in estimates
    # measure a DIFFERENT policy as cheapest -> it must win
    target = next(p for p in OVERLAP_POLICIES if p != model_pick)
    measured = {p: 1.0 for p in OVERLAP_POLICIES}
    measured[target] = 0.125
    pick, est = auto_overlap_policy(1e-3, 5e-4, 5e-4, 2, 4, measured=measured)
    assert pick == target and pick != model_pick
    assert est[target] == 0.125  # the audit table carries measured values

    # restrict-to-measured: a single measured policy wins outright even
    # when the model thinks another is faster (no cross-scale mixing)
    lone = next(p for p in OVERLAP_POLICIES if p != model_pick)
    pick, est = auto_overlap_policy(
        1e-3, 5e-4, 5e-4, 2, 4, measured={lone: 999.0}
    )
    assert pick == lone and est[lone] == 999.0


def test_seam_prior_round_seconds_prefers_measured():
    g = gnp_graph(30, 0.2, seed=1)
    part = partition_2d(g, 2, 2)
    model_prior = prior_round_seconds(part, "sparse", 8, "none")
    measured_prior = prior_round_seconds(
        part, "sparse", 8, "none", measured_level_s=0.1234
    )
    assert measured_prior == pytest.approx(0.1234 * PRIOR_LEVELS)
    assert measured_prior != model_prior


# (the fourth seam — the BCSR tile pick — is
# test_tile_pick_prefers_measured_over_roofline above)


# ------------------------------------------------ batch-size validation
def test_validate_batch_size_rejects_nonpositive():
    with pytest.raises(ValueError, match="batch_size"):
        validate_batch_size(0)
    g = gnp_graph(10, 0.3, seed=1)
    with pytest.raises(ValueError, match="batch_size"):
        betweenness_centrality(g, batch_size=0)
    with pytest.raises(ValueError, match="batch_size"):
        build_schedule(g, batch_size=-3)
    # the distributed entrypoint rejects before touching the mesh
    with pytest.raises(ValueError, match="batch_size"):
        distributed_betweenness_centrality(g, None, batch_size=-1)


def test_validate_batch_size_pad_hint(caplog):
    with caplog.at_level(logging.WARNING, logger="repro.core.scheduler"):
        assert validate_batch_size(48) == 48  # pads to 128: 80 dead lanes
    assert any("wasted MXU" in r.message for r in caplog.records)
    assert any("128" in r.message for r in caplog.records)

    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="repro.core.scheduler"):
        validate_batch_size(128)  # exact tile: no padding
        validate_batch_size(65)   # pads 63 lanes: less than half a tile
    assert not caplog.records


# ------------------------------------- eccentricity + cost-packed deals
def test_bfs_depths():
    np.testing.assert_array_equal(
        bfs_depths(path_graph(5), 0), [0, 1, 2, 3, 4]
    )
    g = disjoint_union(path_graph(3), complete_graph(3))
    depth = bfs_depths(g, 0)
    assert depth[2] == 2 and (depth[3:] == -1).all()


def test_estimate_eccentricities_orders_deep_above_shallow():
    g = disjoint_union(path_graph(8), complete_graph(8))
    ecc = estimate_eccentricities(g, num_samples=4, seed=0)
    # farthest-first hits the path endpoints: the full length is seen
    assert ecc[:8].max() == 7
    # every component got a landmark, so the clique measures its true 1
    assert (ecc[8:] == 1).all()
    # and every path vertex sorts above every clique vertex
    assert ecc[:8].min() > ecc[8:].max()


def test_estimate_eccentricities_covers_many_components_past_budget():
    # 6 components but a 2-sample budget: coverage still guaranteed
    g = disjoint_union(*[path_graph(5) for _ in range(6)])
    ecc = estimate_eccentricities(g, num_samples=2, seed=3)
    assert (ecc.reshape(6, 5).max(axis=1) >= 2).all()


def test_split_rounds_cost_packed_deal():
    costs = [7, 1, 7, 1, 7, 1, 7, 1]
    # costliest-first row-major deal — the redeal_rounds shape, seeded
    # from the prior instead of the EWMA
    assert split_rounds(8, 2, round_costs=costs) == [[0, 4, 1, 5], [2, 6, 3, 7]]
    assert split_rounds(8, 2, committed={0, 1}, round_costs=costs) == [
        [2, 6, 5],
        [4, 3, 7],
    ]
    # exactly-once: the deal is a permutation
    assert sorted(
        r for q in split_rounds(8, 3, round_costs=costs) for r in q
    ) == list(range(8))
    # no costs -> the legacy interleaved deal, unchanged
    assert split_rounds(7, 2) == [[0, 2, 4, 6], [1, 3, 5]]
    with pytest.raises(ValueError, match="costs"):
        split_rounds(8, 2, round_costs=[1.0])


def test_build_schedule_root_order_validation():
    g = gnp_graph(10, 0.3, seed=1)
    with pytest.raises(ValueError, match="root_order"):
        build_schedule(g, root_order="degree")
    assert set(ROOT_ORDERS) == {"id", "eccentricity"}
    schedule, _, _, _ = build_schedule(g, batch_size=4)
    assert schedule.round_depths is None  # id order carries no prior


def _sum_traversal_levels(graph, schedule):
    """Total level iterations of running the schedule's rounds on the
    single-device dense engine (the depth-divergence cost metric)."""
    adjacency = jnp.asarray(graph.dense_adjacency(np.float32))
    omega = jnp.zeros(graph.n, jnp.float32)
    total = 0
    for r in schedule.rounds:
        _, _, _, levels = traversal_round(
            engine.make_dense_operator(adjacency),
            jnp.asarray(r.sources),
            jnp.asarray(r.derived),
            omega,
        )
        total += int(levels)
    return total


def test_ecc_packed_rounds_cut_total_levels_and_keep_parity():
    # alternating path/clique blocks: the id-order deal mixes one deep
    # and one shallow component per round, the eccentricity deal packs
    # deep with deep — measurably fewer total level iterations
    g = skewed_depth_graph(2, 8)  # n=32: path, K8, path, K8
    batch = 16
    sched_id, _, _, _ = build_schedule(g, batch_size=batch)
    sched_ecc, prep, _, _ = build_schedule(
        g, batch_size=batch, root_order="eccentricity"
    )
    assert len(sched_id.rounds) == len(sched_ecc.rounds)
    interleaved = _sum_traversal_levels(g, sched_id)
    packed = _sum_traversal_levels(g, sched_ecc)
    assert packed < interleaved

    # the prior the replica deal consumes: one depth per round, with the
    # deep-root round(s) strictly costlier than the clique round(s)
    depths = sched_ecc.round_depths
    assert depths is not None and len(depths) == len(sched_ecc.rounds)
    assert depths.max() > depths.min()

    # reordering sources never changes BC (additive accumulation)
    adjacency = jnp.asarray(g.dense_adjacency(np.float32))
    omega = jnp.zeros(g.n, jnp.float32)

    def block_fn(sources, derived):
        bc_r, ns, roots, levels = traversal_round(
            engine.make_dense_operator(adjacency), sources[0], derived[0], omega
        )
        return bc_r, ns[None], roots[None], levels[None]

    result = BCDriver(block_fn, sched_ecc, n=g.n, prep=prep).run()
    np.testing.assert_allclose(
        result.bc, brandes_reference(g), rtol=1e-6, atol=1e-6
    )


# ----------------------------------------- depth-divergent rounds, mesh
@needs_mesh
@pytest.mark.parametrize("overlap", list(OVERLAP_POLICIES))
@pytest.mark.parametrize("engine_kind", list(DIST_ENGINE_KINDS))
def test_depth_divergent_batches_distributed(engine_kind, overlap):
    """A round mixing one deep path root with shallow clique roots stays
    at oracle parity for every engine × overlap policy (masked no-op
    levels mask correctly)."""
    from repro.launch.mesh import make_mesh

    g = skewed_depth_graph(2, 8)
    mesh = make_mesh((2, 4), ("data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g, mesh, batch_size=16, engine_kind=engine_kind, overlap=overlap
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-5, atol=1e-5)


@needs_mesh
def test_distributed_autotune_measure_then_cache_roundtrip(tmp_path):
    from repro.launch.mesh import make_mesh

    g = gnp_graph(24, 0.2, seed=3)
    expected = brandes_reference(g)
    mesh = make_mesh((2, 4), ("data", "model"))
    path = tmp_path / "tune.json"

    def run(mode):
        cache = CostCache(path)
        bc, schedule = distributed_betweenness_centrality(
            g, mesh, batch_size=8, engine_kind="sparse", overlap="auto",
            autotune=mode, autotune_cache=cache,
        )
        np.testing.assert_allclose(bc, expected, rtol=1e-5, atol=1e-5)
        # autotune switches the scheduler to eccentricity packing
        assert schedule.round_depths is not None
        return cache

    cold = run("measure")
    assert cold.stores > 0 and path.exists()
    persisted = path.read_bytes()

    warm = run("measure")
    assert warm.hits > 0
    assert warm.stores == 0, "measure-once violated: warm run re-measured"
    assert path.read_bytes() == persisted

    cached = run("cache")
    assert cached.hits > 0 and cached.stores == 0


@needs_mesh
def test_distributed_autotune_off_is_status_quo():
    from repro.launch.mesh import make_mesh

    g = gnp_graph(20, 0.2, seed=4)
    mesh = make_mesh((2, 4), ("data", "model"))
    bc, schedule = distributed_betweenness_centrality(g, mesh, batch_size=8)
    assert schedule.round_depths is None  # id-order schedule, no prior
    np.testing.assert_allclose(
        bc, brandes_reference(g), rtol=1e-5, atol=1e-5
    )


def test_sample_batch_replicates_first_round():
    g = gnp_graph(20, 0.2, seed=4)
    schedule, _, _, _ = build_schedule(g, batch_size=8)
    sources, derived = sample_batch(schedule, fr=2)
    assert sources.shape == (2, 8)
    np.testing.assert_array_equal(sources[0], sources[1])
    assert derived.shape[0] == 2 and derived.shape[2] == 3
    assert Candidate("sparse", "none", 8).key() == "sparse|none|b8|t-"
