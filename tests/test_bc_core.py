"""End-to-end correctness of single-device MGBC vs. the numpy oracle."""
import numpy as np
import pytest

from repro.core import betweenness_centrality, brandes_reference
from repro.graphs import (
    complete_graph,
    cycle_graph,
    disjoint_union,
    gnp_graph,
    grid_graph,
    path_graph,
    rmat_graph,
    road_like_graph,
    star_graph,
)

ALL_HEURISTICS = ["h0", "h1", "h2", "h3"]
ENGINES = ["dense", "sparse"]


def _check(graph, heuristics="h0", engine="dense", batch_size=8, **kw):
    expected = brandes_reference(graph)
    got = betweenness_centrality(
        graph, batch_size=batch_size, heuristics=heuristics, engine_kind=engine, **kw
    )
    np.testing.assert_allclose(got.bc, expected, rtol=1e-5, atol=1e-5)
    return got


# ------------------------------------------------------ structured graphs
@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
def test_path_graph(heuristics):
    # path P_n: BC(v_i) = 2*i*(n-1-i)
    n = 9
    got = _check(path_graph(n), heuristics)
    expected = np.array([2.0 * i * (n - 1 - i) for i in range(n)])
    np.testing.assert_allclose(got.bc, expected, rtol=1e-6)


@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
@pytest.mark.parametrize("n", [4, 5, 8, 13])
def test_cycle_graph(heuristics, n):
    _check(cycle_graph(n), heuristics)


@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
def test_star_graph(heuristics):
    k = 7
    got = _check(star_graph(k), heuristics)
    np.testing.assert_allclose(got.bc[0], k * (k - 1), rtol=1e-6)
    np.testing.assert_allclose(got.bc[1:], 0.0, atol=1e-9)


@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
def test_complete_graph(heuristics):
    got = _check(complete_graph(6), heuristics)
    np.testing.assert_allclose(got.bc, 0.0, atol=1e-9)


@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
def test_grid_graph(heuristics):
    _check(grid_graph(4, 5), heuristics)


# --------------------------------------------------------- random graphs
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gnp(heuristics, engine, seed):
    _check(gnp_graph(24, 0.12, seed=seed), heuristics, engine)


@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
def test_rmat(heuristics):
    _check(rmat_graph(6, 4, seed=3), heuristics, batch_size=16)


@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
def test_road_like(heuristics):
    _check(road_like_graph(4, 4, spur_fraction=0.5, seed=1), heuristics)


# --------------------------------------------------- multiple components
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
def test_multi_component(heuristics, engine):
    g = disjoint_union(
        path_graph(6), star_graph(4), cycle_graph(5), gnp_graph(12, 0.2, seed=7)
    )
    _check(g, heuristics, engine)


@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
def test_k2_components(heuristics):
    # isolated edges: both endpoints are 1-degree — the degenerate case
    g = disjoint_union(path_graph(2), path_graph(2), path_graph(5))
    _check(g, heuristics)


@pytest.mark.parametrize("heuristics", ALL_HEURISTICS)
def test_isolated_vertices(heuristics):
    g = disjoint_union(gnp_graph(10, 0.25, seed=9), path_graph(1), path_graph(1))
    _check(g, heuristics)


# ----------------------------------------------------------- misc modes
def test_static_num_levels_matches_dynamic():
    g = gnp_graph(20, 0.15, seed=4)
    a = betweenness_centrality(g, heuristics="h0", num_levels=None)
    b = betweenness_centrality(g, heuristics="h0", num_levels=22)
    np.testing.assert_allclose(a.bc, b.bc, rtol=1e-6)


def test_batch_size_invariance():
    g = gnp_graph(30, 0.1, seed=5)
    ref = brandes_reference(g)
    for bs in (1, 4, 7, 32, 64):
        got = betweenness_centrality(g, batch_size=bs, heuristics="h3")
        np.testing.assert_allclose(got.bc, ref, rtol=1e-5, atol=1e-5)


def test_two_degree_actually_skips_forward_work():
    g = cycle_graph(12)
    h0 = betweenness_centrality(g, heuristics="h0")
    h2 = betweenness_centrality(g, heuristics="h2")
    assert h2.forward_columns < h0.forward_columns
    # cycle upper bound from the paper: n/2 derivable
    assert h0.forward_columns - h2.forward_columns == 6


def test_one_degree_skips_leaves():
    g = road_like_graph(3, 3, spur_fraction=1.0, seed=0)
    h0 = betweenness_centrality(g, heuristics="h0")
    h1 = betweenness_centrality(g, heuristics="h1")
    assert h1.forward_columns < h0.forward_columns


# ---------------------------------------------- beyond-paper: tree contraction
TREE_MODES = ["h1t", "h3t"]


@pytest.mark.parametrize("heuristics", TREE_MODES)
def test_tree_contraction_path_graph_fully_analytic(heuristics):
    """A path fully contracts: zero rounds, exact analytic scores."""
    n = 11
    got = betweenness_centrality(path_graph(n), heuristics=heuristics)
    expected = np.array([2.0 * i * (n - 1 - i) for i in range(n)])
    np.testing.assert_allclose(got.bc, expected, rtol=1e-6)
    assert got.forward_columns == 0  # every vertex resolved analytically


@pytest.mark.parametrize("heuristics", TREE_MODES)
def test_tree_contraction_random_trees(heuristics):
    rng = np.random.default_rng(5)
    # random tree: attach each vertex to a random earlier vertex
    n = 40
    edges = np.array([[rng.integers(0, i), i] for i in range(1, n)])
    from repro.graphs import Graph

    g = Graph.from_edges(n, edges)
    got = betweenness_centrality(g, heuristics=heuristics)
    np.testing.assert_allclose(got.bc, brandes_reference(g), rtol=1e-6, atol=1e-8)
    assert got.forward_columns == 0


@pytest.mark.parametrize("heuristics", TREE_MODES)
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_tree_contraction_general_graphs(heuristics, seed):
    g = gnp_graph(26, 0.08, seed=seed)  # sparse: trees hang off a core
    got = betweenness_centrality(g, heuristics=heuristics)
    np.testing.assert_allclose(
        got.bc, brandes_reference(g), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("heuristics", TREE_MODES)
def test_tree_contraction_road_like(heuristics):
    g = road_like_graph(5, 5, spur_fraction=1.2, seed=4)
    h0 = betweenness_centrality(g, heuristics="h0")
    got = betweenness_centrality(g, heuristics=heuristics)
    np.testing.assert_allclose(got.bc, h0.bc, rtol=1e-5, atol=1e-5)
    # deep spur chains contract fully — strictly better than single-pass h1
    h1 = betweenness_centrality(g, heuristics="h1")
    assert got.forward_columns < h1.forward_columns


@pytest.mark.parametrize("heuristics", TREE_MODES)
def test_tree_contraction_multi_component(heuristics):
    g = disjoint_union(
        path_graph(7), star_graph(5), cycle_graph(6), gnp_graph(15, 0.15, seed=9),
        path_graph(2),
    )
    got = betweenness_centrality(g, heuristics=heuristics)
    np.testing.assert_allclose(
        got.bc, brandes_reference(g), rtol=1e-5, atol=1e-5
    )


def test_h3_composition_effect_on_suburb_topology():
    """Paper §4.4: 1-degree removal creates new 2-degree vertices, so H3
    derives strictly more than H2 (their RoadNet-PA: +8% derived)."""
    from repro.graphs import suburb_graph

    g = suburb_graph(5, 5, leaf_fraction=0.6, seed=2)
    ref = brandes_reference(g)
    h2 = betweenness_centrality(g, heuristics="h2")
    h3 = betweenness_centrality(g, heuristics="h3")
    np.testing.assert_allclose(h2.bc, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h3.bc, ref, rtol=1e-5, atol=1e-5)
    assert h3.schedule.num_derived > h2.schedule.num_derived
    assert h3.forward_columns < h2.forward_columns


@pytest.mark.parametrize(
    "graph",
    [
        rmat_graph(8, 8, seed=1),
        disjoint_union(gnp_graph(30, 0.1, seed=4), path_graph(9)),
        road_like_graph(6, 7, seed=2),
    ],
    ids=["rmat", "two-components", "road"],
)
def test_csr_oracle_matches_loop_oracle(graph):
    """The whole-level numpy Brandes round (chip-scale oracle) equals the
    per-arc loop round from every source, in float64."""
    from repro.core.brandes_ref import (
        single_source_dependencies,
        single_source_dependencies_csr,
    )

    adj = graph.adjacency_lists()
    row_ptr, col = graph.csr()
    for s in range(graph.n):
        want = single_source_dependencies(adj, graph.n, s)
        got = single_source_dependencies_csr(row_ptr, col, s)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-12)
        np.testing.assert_array_equal(got[2], want[2])
