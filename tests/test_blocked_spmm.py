"""Blocked-sparse (BCSR) layout + kernels + distributed engine parity.

Three layers:

* layout — tile lists of :meth:`TwoDPartition.blocked_sparse` reconstruct
  the dense device blocks exactly (full and per-ring-chunk slices), keep
  the row-sorted / row-complete invariants the kernels rely on, and their
  storage scales with the nonzero-tile count, not the dense block area;
* kernels — ``frontier_spmm_sparse`` / ``dependency_spmm_sparse`` match
  the dense partial kernels on every device block, in full, ring-chunk
  and chunked-``acc`` modes, while iterating only the stored tiles;
* engine — ``engine_kind="pallas_sparse"`` matches ``brandes_reference``
  within 1e-6 on 2x4 and 4x2 meshes for every overlap policy (plus
  ``"auto"``), including sub-cluster meshes with divergent round depths.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import brandes_reference
from repro.core.distributed import (
    check_device_memory,
    distributed_betweenness_centrality,
    distributed_graph_arrays,
    estimate_device_footprint,
    resolve_overlap,
)
from repro.graphs import gnp_graph, rmat_graph
from repro.graphs.partition import default_tile_dim, partition_2d
from repro.kernels import ops
from repro.kernels.blocked_spmm import tiles_to_dense

S = 8


def _layout(graph, R, C, bm=2, bk=2):
    """(partition, full layout, ring layout) — two builds sharing one
    cached arc→tile counting pass; each form materializes only itself."""
    part = partition_2d(graph, R, C)
    return part, part.blocked_sparse(bm, bk), part.blocked_sparse(bm, bk, ring=True)


# ----------------------------------------------------------------- layout
@pytest.mark.parametrize("grid", [(2, 4), (4, 2)])
def test_layout_roundtrip_dense(grid):
    """dense ⊕ reconstruct == original, for the full and ring layouts."""
    g = gnp_graph(26, 0.15, seed=0)
    part, lay, ring_lay = _layout(g, *grid)
    # each form materializes only itself (no discarded double build)
    assert lay.ring_tiles is None and ring_lay.tiles is None
    dense = part.dense_blocks()
    R, C, chunk = part.R, part.C, part.chunk
    m, kdim = C * chunk, R * chunk
    for i in range(R):
        for j in range(C):
            got = tiles_to_dense(
                jnp.asarray(lay.tiles[i, j]),
                jnp.asarray(lay.tile_rows[i, j]),
                jnp.asarray(lay.tile_cols[i, j]),
                m,
                kdim,
            )
            np.testing.assert_array_equal(np.asarray(got), dense[i, j])
            # ring slices re-based per chunk: sum of slot reconstructions
            ring = np.zeros((m, kdim), np.float32)
            for r in range(R):
                slot = tiles_to_dense(
                    jnp.asarray(ring_lay.ring_tiles[i, j, r]),
                    jnp.asarray(ring_lay.ring_tile_rows[i, j, r]),
                    jnp.asarray(ring_lay.ring_tile_cols[i, j, r]),
                    m,
                    chunk,
                )
                ring[:, r * chunk : (r + 1) * chunk] += np.asarray(slot)
            np.testing.assert_array_equal(ring, dense[i, j])


def test_layout_invariants_and_validation():
    g = gnp_graph(26, 0.15, seed=0)
    part, lay, ring_lay = _layout(g, 2, 4)
    num_tr = lay.num_tile_rows
    for i in range(2):
        for j in range(4):
            rows = lay.tile_rows[i, j]
            assert np.all(np.diff(rows) >= 0)  # row-sorted
            assert set(range(num_tr)) <= set(rows.tolist())  # row-complete
            for r in range(2):
                ring_rows = ring_lay.ring_tile_rows[i, j, r]
                assert np.all(np.diff(ring_rows) >= 0)
                assert set(range(num_tr)) <= set(ring_rows.tolist())
    with pytest.raises(ValueError):
        part.blocked_sparse(3, 2)  # 3 does not divide chunk=4
    assert default_tile_dim(128) == 128
    assert default_tile_dim(48) == 48  # lane-aligned divisor preferred
    assert default_tile_dim(7) == 7  # falls back to any divisor


def test_layout_memory_scales_with_nnz_tiles():
    """On a sparse RMAT block the stored-tile footprint is a small
    fraction of the dense block — the O(nnz-tiles) memory claim."""
    g = rmat_graph(10, 4, seed=1)
    part = partition_2d(g, 2, 4)
    lay = part.blocked_sparse(8, 8)
    dense_tiles = lay.num_tile_rows * lay.num_tile_cols
    assert int(lay.nnz_tiles.max()) < dense_tiles // 2
    dense_bytes = (part.C * part.chunk) * (part.R * part.chunk) * 4
    assert lay.adjacency_bytes() < dense_bytes
    # stored count tracks nnz tiles (padding bounded by the worst cell
    # plus the one-filler-per-empty-row invariant)
    stored = lay.tiles.shape[2]
    assert stored <= int(lay.nnz_tiles.max()) + lay.num_tile_rows
    assert lay.nnz_tiles.sum() == part.nnz_tile_counts(8, 8).sum()


def test_blocked_sparse_counts_match_materialized_layout():
    """The no-materialize accounting the memory guard prices must equal
    the shipped layout byte-for-byte (full and ring forms)."""
    g = rmat_graph(10, 4, seed=1)
    part = partition_2d(g, 2, 4)
    counts = part.blocked_sparse_counts(8, 8)
    assert counts["nnz_max"] == int(part.nnz_tile_counts(8, 8).max())
    for ring in (False, True):
        lay = part.blocked_sparse(8, 8, ring=ring)
        key = "ring" if ring else "full"
        assert counts[f"bytes_{key}"] == lay.adjacency_bytes()
        assert counts["nnz_total"] == int(lay.nnz_tiles.sum())
        arr = lay.ring_tiles if ring else lay.tiles
        stored = arr.shape[3] * arr.shape[2] if ring else arr.shape[2]
        assert counts[f"stored_tiles_{key}"] == stored


def test_footprint_prices_ring_layouts():
    """Under a ring overlap policy the guard must price the ring layouts
    (R padded slots / slices), which can only be larger than the flat
    forms it prices for the barrier schedule."""
    g = rmat_graph(10, 4, seed=1)
    part = partition_2d(g, 2, 4)
    for kind in ("sparse", "pallas_sparse"):
        flat = estimate_device_footprint(part, kind, 16, bm=8, bk=8)
        ring = estimate_device_footprint(
            part, kind, 16, bm=8, bk=8, overlap="expand"
        )
        assert ring["adjacency_bytes"] >= flat["adjacency_bytes"]
    # sparse arc ring pricing matches the materialized ring layout
    ring_src, _ = part.ring_arcs()
    want = 2 * ring_src.shape[2] * ring_src.shape[3] * 4
    got = estimate_device_footprint(part, "sparse", 16, overlap="expand")
    assert got["adjacency_bytes"] == want


@pytest.mark.parametrize("kind", ["sparse", "pallas", "pallas_sparse", "pallas_hybrid"])
@pytest.mark.parametrize("overlap", ["none", "expand+fold"])
def test_footprint_prices_ring_slot_copies_of_the_tile_list_only(kind, overlap):
    """No engine's ring round copies its ring steps' tile slices any more
    (the blocked-sparse kernels read their slot in place;
    ``tests/test_tpu_compile.py`` reads the compiled round): the guard
    prices the resident adjacency and the state, and no copy."""
    part = partition_2d(rmat_graph(10, 4, seed=1), 2, 2)
    foot = estimate_device_footprint(part, kind, 16, bm=8, bk=8, overlap=overlap)
    assert "ring_copy_bytes" not in foot
    assert foot["total_bytes"] == foot["adjacency_bytes"] + foot["state_bytes"]


# ---------------------------------------------------------------- kernels
@pytest.mark.parametrize("use_pallas", [True, False])
def test_sparse_kernels_match_dense_partials(rng, use_pallas):
    g = gnp_graph(26, 0.15, seed=0)
    part, lay, _ = _layout(g, 2, 4)
    dense = part.dense_blocks()
    chunk = part.chunk
    kdim, m = 2 * chunk, 4 * chunk
    sigma = jnp.asarray(rng.integers(0, 5, (kdim, S)), jnp.float32)
    depth = jnp.asarray(rng.integers(-1, 4, (kdim, S)), jnp.int32)
    delta = jnp.asarray(rng.normal(size=(kdim, S)), jnp.float32)
    omega = jnp.asarray(rng.integers(0, 3, kdim), jnp.float32)
    acc0 = jnp.asarray(rng.normal(size=(m, S)), jnp.float32)
    lvl = 2
    for i in range(2):
        for j in range(4):
            tiles, tr, tc = (
                jnp.asarray(a[i, j])
                for a in (lay.tiles, lay.tile_rows, lay.tile_cols)
            )
            a_dense = jnp.asarray(dense[i, j])
            want_f = ops.frontier_spmm_partial(a_dense, sigma, depth, lvl, interpret=True)
            got_f = ops.frontier_spmm_sparse(
                tiles, tr, tc, sigma, depth, lvl, m=m,
                use_pallas=use_pallas, interpret=True,
            )
            np.testing.assert_allclose(got_f, want_f, rtol=1e-5, atol=1e-6)
            # chunked-acc mode: the ring's running combine
            got_acc = ops.frontier_spmm_sparse(
                tiles, tr, tc, sigma, depth, lvl, m=m, acc=acc0,
                use_pallas=use_pallas, interpret=True,
            )
            np.testing.assert_allclose(got_acc, acc0 + want_f, rtol=1e-5, atol=1e-5)
            want_b = ops.dependency_spmm_partial(
                a_dense, sigma, depth, delta, omega, lvl, interpret=True
            )
            got_b = ops.dependency_spmm_sparse(
                tiles, tr, tc, sigma, depth, delta, omega, lvl, m=m,
                use_pallas=use_pallas, interpret=True,
            )
            np.testing.assert_allclose(got_b, want_b, rtol=1e-5, atol=1e-6)


def test_ring_chunk_composition_matches_full(rng):
    """R chunked-acc steps over the ring slices == one full-block call."""
    g = gnp_graph(26, 0.15, seed=0)
    part, lay, ring_lay = _layout(g, 2, 4)
    chunk = part.chunk
    kdim, m = 2 * chunk, 4 * chunk
    sigma = jnp.asarray(rng.integers(0, 5, (kdim, S)), jnp.float32)
    depth = jnp.asarray(rng.integers(-1, 4, (kdim, S)), jnp.int32)
    i, j = 1, 2
    tiles, tr, tc = (
        jnp.asarray(a[i, j]) for a in (lay.tiles, lay.tile_rows, lay.tile_cols)
    )
    want = ops.frontier_spmm_sparse(
        tiles, tr, tc, sigma, depth, 2, m=m, interpret=True
    )
    acc = jnp.zeros((m, S), jnp.float32)
    for r in range(2):
        acc = ops.frontier_spmm_sparse(
            jnp.asarray(ring_lay.ring_tiles[i, j, r]),
            jnp.asarray(ring_lay.ring_tile_rows[i, j, r]),
            jnp.asarray(ring_lay.ring_tile_cols[i, j, r]),
            sigma[r * chunk : (r + 1) * chunk],
            depth[r * chunk : (r + 1) * chunk],
            2,
            m=m,
            acc=acc,
            interpret=True,
        )
    np.testing.assert_allclose(acc, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize(
    "kernel,r,with_acc,use_pallas",
    [
        (kernel, r, with_acc, True)
        for kernel in ("frontier", "dependency")
        for r in (0, 1)
        for with_acc in (False, True)
    ]
    + [("dependency", 1, True, False)],
)
def test_tile_offset_reads_the_ring_slot_in_place(rng, kernel, r, with_acc, use_pallas):
    """A call on the whole [R·Tr, bm, bk] ring layout at ``tile_offset``
    r·Tr equals, bit for bit, the call on slot r's own [Tr, bm, bk] tiles."""
    g = gnp_graph(26, 0.15, seed=0)
    part, _, ring_lay = _layout(g, 2, 4)
    chunk, m = part.chunk, 4 * part.chunk
    i, j = 1, 2
    ring_tiles = jnp.asarray(ring_lay.ring_tiles[i, j])  # [R, Tr, bm, bk]
    R, tr, bm, bk = ring_tiles.shape
    assert R == 2
    rows = jnp.asarray(ring_lay.ring_tile_rows[i, j, r])
    cols = jnp.asarray(ring_lay.ring_tile_cols[i, j, r])
    sigma = jnp.asarray(rng.integers(0, 5, (chunk, S)), jnp.float32)
    depth = jnp.asarray(rng.integers(-1, 4, (chunk, S)), jnp.int32)
    delta = jnp.asarray(rng.normal(size=(chunk, S)), jnp.float32)
    omega = jnp.asarray(rng.integers(0, 3, chunk), jnp.float32)
    acc = jnp.asarray(rng.normal(size=(m, S)), jnp.float32) if with_acc else None
    kw = dict(m=m, acc=acc, use_pallas=use_pallas, interpret=True)

    def call(tiles, off):
        if kernel == "frontier":
            return ops.frontier_spmm_sparse(
                tiles, rows, cols, sigma, depth, 2, tile_offset=off, **kw)
        return ops.dependency_spmm_sparse(
            tiles, rows, cols, sigma, depth, delta, omega, 2, tile_offset=off, **kw)

    got = call(ring_tiles.reshape(R * tr, bm, bk), r * tr)
    want = call(ring_tiles[r], 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(want)).sum() > 0


def test_empty_tiles_are_skipped(rng):
    """A block-diagonal graph stores ~1/num_chunks of the dense tiles,
    and filler tiles (empty rows / padding) do not perturb the product."""
    # two disjoint cliques → strongly block-structured adjacency
    from repro.graphs import disjoint_union, gnp_graph as gnp

    g = disjoint_union(gnp(16, 0.9, seed=1), gnp(16, 0.9, seed=2))
    part, lay, _ = _layout(g, 2, 4, bm=2, bk=2)
    dense_tiles = lay.num_tile_rows * lay.num_tile_cols
    assert int(lay.nnz_tiles.sum()) < dense_tiles * 8 // 2  # mostly empty
    chunk = part.chunk
    kdim, m = 2 * chunk, 4 * chunk
    sigma = jnp.asarray(rng.integers(0, 5, (kdim, S)), jnp.float32)
    depth = jnp.asarray(rng.integers(-1, 4, (kdim, S)), jnp.int32)
    dense = part.dense_blocks()
    for i in range(2):
        for j in range(4):
            want = ops.frontier_spmm_partial(
                jnp.asarray(dense[i, j]), sigma, depth, 2, interpret=True
            )
            got = ops.frontier_spmm_sparse(
                jnp.asarray(lay.tiles[i, j]),
                jnp.asarray(lay.tile_rows[i, j]),
                jnp.asarray(lay.tile_cols[i, j]),
                sigma,
                depth,
                2,
                m=m,
                interpret=True,
            )
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ----------------------------------------------------- distributed engine
needs_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host devices"
)


@needs_devices
@pytest.mark.parametrize("grid", [(2, 4), (4, 2)])
@pytest.mark.parametrize("overlap", ["none", "expand", "expand+fold", "auto"])
def test_pallas_sparse_matches_oracle(grid, overlap):
    from repro.launch.mesh import make_mesh

    g = gnp_graph(26, 0.15, seed=0)
    mesh = make_mesh(grid, ("data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g,
        mesh,
        heuristics="h3",
        batch_size=8,
        engine_kind="pallas_sparse",
        overlap=overlap,
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-6, atol=1e-6)


@needs_devices
@pytest.mark.parametrize("overlap", ["expand", "expand+fold"])
def test_pallas_sparse_subcluster_divergent_depths(overlap):
    """Replicas with divergent data-dependent level counts (41-level path
    round vs 2-level G(n,p) round) must not deadlock the tile ring."""
    from repro.graphs import disjoint_union, path_graph
    from repro.launch.mesh import make_mesh

    g = disjoint_union(path_graph(40), gnp_graph(16, 0.3, seed=4))
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g,
        mesh,
        replica_axis="pod",
        batch_size=8,
        engine_kind="pallas_sparse",
        overlap=overlap,
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-6, atol=1e-6)


def test_graph_arrays_layouts():
    g = gnp_graph(26, 0.15, seed=0)
    part = partition_2d(g, 2, 4)
    full = distributed_graph_arrays(part, "pallas_sparse", "none")
    assert len(full) == 3 and full[0].ndim == 5
    ring = distributed_graph_arrays(part, "pallas_sparse", "expand")
    assert len(ring) == 3 and ring[0].ndim == 6 and ring[0].shape[2] == part.R


# ------------------------------------------- memory guard + auto overlap
def test_footprint_sparse_below_dense_and_guard_fires():
    # 8x8 tiles: production-default 128 tiles are larger than this test
    # graph's whole chunk, so pick a tile that resolves its sparsity
    g = rmat_graph(10, 4, seed=1)
    part = partition_2d(g, 2, 4)
    dense = estimate_device_footprint(part, "pallas", 16)
    sparse = estimate_device_footprint(part, "pallas_sparse", 16, bm=8, bk=8)
    assert sparse["adjacency_bytes"] < dense["adjacency_bytes"]
    # budget between the two engines: dense errors and suggests sparse
    budget = (dense["total_bytes"] + sparse["total_bytes"]) / 2
    with pytest.raises(MemoryError, match="pallas_sparse"):
        check_device_memory(part, "pallas", 16, budget, bm=8, bk=8)
    check_device_memory(part, "pallas_sparse", 16, budget, bm=8, bk=8)  # fits
    check_device_memory(part, "pallas", 16, None)  # guard disarmed


def test_resolve_overlap_auto_and_passthrough():
    g = gnp_graph(26, 0.15, seed=0)
    part = partition_2d(g, 2, 4)
    for kind in ("sparse", "pallas", "pallas_sparse"):
        assert resolve_overlap("auto", part, kind, 8) in (
            "none",
            "expand",
            "expand+fold",
        )
    assert resolve_overlap("expand", part, "sparse", 8) == "expand"
    with pytest.raises(ValueError):
        resolve_overlap("ring", part, "sparse", 8)
