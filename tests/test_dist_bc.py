"""Distributed 2-D MGBC == numpy oracle, on an 8-host-device mesh."""
import numpy as np
import pytest

import jax

from repro.core import brandes_reference
from repro.core.distributed import distributed_betweenness_centrality
from repro.graphs import (
    cycle_graph,
    disjoint_union,
    gnp_graph,
    grid_graph,
    path_graph,
    rmat_graph,
    road_like_graph,
    star_graph,
)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host devices"
)


def _mesh(shape, names):
    from repro.launch.mesh import make_mesh

    return make_mesh(shape, names)


def _check(graph, mesh_shape=(2, 4), heuristics="h0", replica=False, **kw):
    if replica:
        mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
        bc, _ = distributed_betweenness_centrality(
            graph,
            mesh,
            replica_axis="pod",
            heuristics=heuristics,
            **kw,
        )
    else:
        mesh = _mesh(mesh_shape, ("data", "model"))
        bc, _ = distributed_betweenness_centrality(
            graph, mesh, heuristics=heuristics, **kw
        )
    expected = brandes_reference(graph)
    np.testing.assert_allclose(bc, expected, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("heuristics", ["h0", "h1", "h2", "h3"])
def test_gnp_2x4(heuristics):
    _check(gnp_graph(26, 0.15, seed=0), (2, 4), heuristics)


@pytest.mark.parametrize("heuristics", ["h0", "h3"])
def test_gnp_4x2(heuristics):
    _check(gnp_graph(23, 0.2, seed=1), (4, 2), heuristics)


@pytest.mark.parametrize("heuristics", ["h0", "h1", "h2", "h3"])
def test_subcluster_replicas(heuristics):
    _check(gnp_graph(25, 0.15, seed=2), heuristics=heuristics, replica=True)


def test_structured_graphs():
    _check(grid_graph(4, 5), (2, 4))
    _check(cycle_graph(17), (2, 4), "h2")
    _check(star_graph(9), (2, 4), "h1")


def test_multi_component_distributed():
    g = disjoint_union(path_graph(7), star_graph(5), gnp_graph(14, 0.2, seed=3))
    _check(g, (2, 4), "h3")


def test_rmat_distributed():
    _check(rmat_graph(6, 4, seed=5), (2, 4), "h3", batch_size=8)


def test_road_like_distributed():
    _check(road_like_graph(4, 4, spur_fraction=0.6, seed=2), (2, 4), "h3")


@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2)])
@pytest.mark.parametrize("engine_kind", ["pallas", "pallas_bf16"])
def test_pallas_dense_block_engine(mesh_shape, engine_kind):
    """Fused Pallas kernels as the 2-D block-local compute == oracle."""
    g = gnp_graph(26, 0.15, seed=0)
    mesh = _mesh(mesh_shape, ("data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g, mesh, heuristics="h3", batch_size=8, engine_kind=engine_kind
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("engine_kind", ["pallas"])
def test_pallas_dense_block_engine_subcluster(engine_kind):
    g = gnp_graph(25, 0.15, seed=2)
    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g, mesh, replica_axis="pod", heuristics="h0", engine_kind=engine_kind
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-6, atol=1e-6)


def test_static_levels_distributed():
    g = gnp_graph(20, 0.18, seed=7)
    mesh = _mesh((2, 4), ("data", "model"))
    bc, _ = distributed_betweenness_centrality(g, mesh, num_levels=22)
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-5, atol=1e-5)


def test_unfused_backward_matches():
    from repro.core.distributed import make_distributed_round_fn
    from repro.graphs.partition import partition_2d
    from repro.core.scheduler import build_schedule
    import jax.numpy as jnp

    g = gnp_graph(24, 0.2, seed=9)
    mesh = _mesh((2, 4), ("data", "model"))
    schedule, _, residual, omega = build_schedule(g, batch_size=24)
    part = partition_2d(residual, 2, 4)
    omega_pad = np.zeros(part.n_pad, np.float32)
    outs = []
    for fuse in (True, False):
        fn = make_distributed_round_fn(part, mesh, fuse_backward_payload=fuse)
        rnd = schedule.rounds[0]
        bc_r, _, _, _ = fn(
            jnp.asarray(part.src_local),
            jnp.asarray(part.dst_local),
            jnp.asarray(omega_pad),
            jnp.asarray(rnd.sources[None]),
            jnp.asarray(rnd.derived[None]),
        )
        outs.append(np.asarray(bc_r))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)


def test_distributed_one_degree_matches_host():
    from repro.core.distributed import one_degree_reduce_distributed
    from repro.core.heuristics.one_degree import one_degree_reduce

    g = road_like_graph(4, 4, spur_fraction=0.8, seed=3)
    mesh = _mesh((2, 4), ("data", "model"))
    omega_d, removed_d = one_degree_reduce_distributed(g, mesh, ("data", "model"))
    host = one_degree_reduce(g)
    np.testing.assert_array_equal(omega_d, host.omega)
    # residual graphs identical
    res_d = g.subgraph_mask(~removed_d)
    np.testing.assert_array_equal(res_d.src, host.residual.src)
    np.testing.assert_array_equal(res_d.dst, host.residual.dst)


@pytest.mark.parametrize("heuristics", ["h1t", "h3t"])
def test_tree_contraction_distributed(heuristics):
    g = road_like_graph(4, 4, spur_fraction=1.0, seed=6)
    _check(g, (2, 4), heuristics)


@pytest.mark.parametrize("engine_kind", ["sparse", "pallas", "pallas_sparse"])
def test_one_by_one_mesh(engine_kind):
    """A 1x1 mesh — one chip driven through the distributed code path —
    is a valid 2-D decomposition: R = C = 1, collectives over one device."""
    g = rmat_graph(6, 8, seed=1)
    mesh = _mesh((1, 1), ("data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g, mesh, heuristics="h0", batch_size=16, engine_kind=engine_kind
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("engine_kind,overlap", [
    ("sparse", "none"), ("pallas", "none"), ("pallas_sparse", "expand+fold"),
])
def test_graph_operands_land_on_their_own_devices(engine_kind, overlap):
    """With a mesh, each device is handed only its own [R, C] cell of
    every graph operand — nothing is staged whole on one device."""
    from repro.core.distributed import distributed_graph_arrays
    from repro.graphs.partition import partition_2d

    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    part = partition_2d(gnp_graph(40, 0.1, seed=3), 2, 2)
    for arr in distributed_graph_arrays(part, engine_kind, overlap, mesh=mesh):
        shards = arr.addressable_shards
        assert {s.device for s in shards} == set(mesh.devices.flat)
        for s in shards:
            assert s.data.shape[:2] == (1, 1)  # one grid cell per device
            (i,), (j,) = (
                range(arr.shape[0])[s.index[0]], range(arr.shape[1])[s.index[1]]
            )
            pod, row, col = np.argwhere(mesh.devices == s.device)[0]
            assert (i, j) == (row, col)
