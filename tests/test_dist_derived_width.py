"""The backward state's width follows the schedule's 2-degree claims.

``build_schedule`` sizes ``derived_per_round`` from the claimed triples
(``min(batch_size // 2, claims)``), so a schedule that claims nothing
("h0", "h1", sampled runs, a graph without 2-degree vertices) derives
nothing and the dependency SpMM runs on exactly ``batch_size`` columns.
These tests pin that width, that rounds and scores do not change, and
that every loop (static, straggler, checkpoint resume) runs with k = 0.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import brandes_reference, engine
from repro.core.bc import make_round_fn
from repro.core.distributed import distributed_betweenness_centrality
from repro.core.driver import BCDriver
from repro.core.heuristics.two_degree import claim_two_degree
from repro.core.scheduler import build_schedule
from repro.distributed.fault_tolerance import BCCheckpoint, schedule_fingerprint
from repro.graphs import complete_graph, cycle_graph, gnp_graph, rmat_graph, road_like_graph
from repro.serving.sampling import BlockBudgetStop, eligible_roots, plan_sampling

needs8 = pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 host devices")


def _claims(residual) -> int:
    deg = residual.degrees()
    return len(claim_two_degree(deg, residual.adjacency_lists(), deg >= 1))


def _dense_driver_bc(graph, schedule, prep, residual, omega_i):
    adjacency = jnp.asarray(residual.dense_adjacency(np.float32))
    omega = jnp.asarray(omega_i, jnp.float32)
    base = jax.jit(make_round_fn(lambda: engine.make_dense_operator(adjacency), graph.n))

    def fn(sources, derived):
        bc_r, ns, roots, levels = base(sources[0], derived[0], omega)
        return bc_r, ns[None], roots[None], levels[None]

    return BCDriver(fn, schedule, n=graph.n, prep=prep).run().bc


def _schedule_span(result):
    (sched,) = [s for s in result.spans if s.name == "bc.setup.schedule"]
    return sched.attrs


# ------------------------------------------------------- the schedule
@pytest.mark.parametrize("heuristics,graph,batch_size,claimed", [
    ("h0", rmat_graph(6, 8, seed=1), 16, False),
    ("h1", rmat_graph(6, 8, seed=1), 16, False),
    ("h1t", road_like_graph(4, 4, spur_fraction=0.6, seed=2), 8, False),
    ("h2", complete_graph(6), 8, False),  # no 2-degree vertex to claim
    ("h2", cycle_graph(17), 4, True),  # the batch binds: k = batch_size // 2
    ("h2", cycle_graph(17), 32, True),  # the claims bind: k = claims
    ("h3", road_like_graph(4, 4, spur_fraction=0.6, seed=2), 16, True),
], ids=["h0", "h1", "h1t", "h2-none", "h2-batch-binds", "h2-claims-bind", "h3"])
def test_default_derived_width_follows_the_claims(heuristics, graph, batch_size, claimed):
    schedule, prep, residual, omega_i = build_schedule(
        graph, batch_size=batch_size, heuristics=heuristics
    )
    claims = _claims(residual) if heuristics in ("h2", "h3") else 0
    k = schedule.derived_per_round
    assert k == min(batch_size // 2, claims) and (k > 0) == claimed
    assert all(r.derived.shape == (k, 3) for r in schedule.rounds)
    assert f"_k{k}_" in schedule_fingerprint(graph.n, schedule)
    # the old fixed width packs the same rounds in the same order
    wide, _, _, _ = build_schedule(
        graph, batch_size=batch_size, heuristics=heuristics,
        derived_per_round=max(1, batch_size // 2),
    )
    assert len(wide.rounds) == len(schedule.rounds)
    for got, old in zip(schedule.rounds, wide.rounds):
        np.testing.assert_array_equal(got.sources, old.sources)
        np.testing.assert_array_equal(got.derived, old.derived[:k])
        assert (old.derived[k:] == -1).all()
    np.testing.assert_allclose(
        _dense_driver_bc(graph, schedule, prep, residual, omega_i),
        brandes_reference(graph), rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("derived_per_round", [0, 1, 3, 8])
def test_explicit_derived_per_round_is_honoured(derived_per_round):
    g = cycle_graph(17)
    schedule, prep, residual, omega_i = build_schedule(
        g, batch_size=8, heuristics="h2", derived_per_round=derived_per_round
    )
    assert schedule.derived_per_round == derived_per_round
    assert all(r.derived.shape == (derived_per_round, 3) for r in schedule.rounds)
    assert (schedule.num_derived > 0) == (derived_per_round > 0)
    covered = [int(v) for r in schedule.rounds for v in r.sources if v >= 0]
    covered += [int(c) for r in schedule.rounds for c in r.derived[:, 0] if c >= 0]
    assert sorted(covered) == list(range(g.n))  # every root once, derived or not
    np.testing.assert_allclose(
        _dense_driver_bc(g, schedule, prep, residual, omega_i),
        brandes_reference(g), rtol=1e-5, atol=1e-5,
    )


# --------------------------------------------------- through the entry
def _recording_dependency_spmm(monkeypatch):
    from repro.kernels import ops as kops

    widths = []
    original = kops.dependency_spmm_sparse

    def recorded(tiles, tile_rows, tile_cols, sigma, *args, **kwargs):
        widths.append(sigma.shape[1])
        return original(tiles, tile_rows, tile_cols, sigma, *args, **kwargs)

    monkeypatch.setattr(kops, "dependency_spmm_sparse", recorded)
    return widths


@needs8
@pytest.mark.parametrize("engine_kind,grid", [("pallas_sparse", (1, 1)), ("sparse", (2, 2))])
@pytest.mark.parametrize("sampled", [False, True], ids=["full", "sampled"])
def test_h0_backward_state_is_the_batch(monkeypatch, engine_kind, grid, sampled):
    from repro.launch.mesh import make_mesh

    widths = _recording_dependency_spmm(monkeypatch)
    g = rmat_graph(6, 8, seed=1)
    batch_size = 16
    sampling = dict(sampling="fixed", sample_k=40, sample_seed=3) if sampled else {}
    result = distributed_betweenness_centrality(
        g, make_mesh(grid, ("data", "model")), heuristics="h0", batch_size=batch_size,
        engine_kind=engine_kind, full_result=True, **sampling,
    )
    assert _schedule_span(result) == {"derived_per_round": 0, "width": batch_size}
    if engine_kind == "pallas_sparse":
        assert widths and set(widths) == {batch_size}
    if sampled:
        plan = plan_sampling(eligible_roots(g), "fixed", None, 40, 3)
        expected = plan.scale * brandes_reference(g, sources=plan.roots)
        assert result.roots_accumulated == 40
    else:
        expected = brandes_reference(g)
    np.testing.assert_allclose(result.bc, expected, rtol=1e-5, atol=1e-4)


@needs8
@pytest.mark.parametrize("engine_kind,grid", [("pallas_sparse", (1, 1)), ("sparse", (2, 2))])
def test_h2_claims_keep_their_derived_columns(monkeypatch, engine_kind, grid):
    from repro.launch.mesh import make_mesh

    widths = _recording_dependency_spmm(monkeypatch)
    g = road_like_graph(4, 4, spur_fraction=0.6, seed=2)
    batch_size = 16
    result = distributed_betweenness_centrality(
        g, make_mesh(grid, ("data", "model")), heuristics="h2", batch_size=batch_size,
        engine_kind=engine_kind, full_result=True,
    )
    k = min(batch_size // 2, _claims(g))
    assert k > 0
    assert _schedule_span(result) == {"derived_per_round": k, "width": batch_size + k}
    if engine_kind == "pallas_sparse":
        assert widths and set(widths) == {batch_size + k}
    np.testing.assert_allclose(result.bc, brandes_reference(g), rtol=1e-5, atol=1e-5)


@needs8
def test_h0_straggler_snapshot_and_resume(tmp_path):
    """k = 0 through the straggler loop on a replicated mesh: a run cut
    after one block leaves a snapshot, and the resumed run finishes the
    exact scores."""
    from repro.launch.mesh import make_mesh

    g = gnp_graph(25, 0.15, seed=2)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    ckpt = BCCheckpoint(str(tmp_path / "bc.npz"))
    kw = dict(
        replica_axis="pod", heuristics="h0", batch_size=4, straggler="redeal",
        sampling="fixed", sample_frac=1.0, checkpoint=ckpt, full_result=True,
    )
    partial = distributed_betweenness_centrality(
        g, mesh, stop_rule=BlockBudgetStop(1), **kw
    )
    assert partial.stopped_early
    assert _schedule_span(partial) == {"derived_per_round": 0, "width": 4}
    _, _, by_lane = ckpt.load_namespaced()
    committed = {rid for lane in by_lane for rid in lane}
    assert 0 < len(committed)
    resumed = distributed_betweenness_centrality(g, mesh, **kw)
    assert not resumed.stopped_early
    assert resumed.rounds_run + len(committed) == len(
        build_schedule(g, batch_size=4)[0].rounds
    )
    np.testing.assert_allclose(resumed.bc, brandes_reference(g), rtol=1e-5, atol=1e-5)
