"""Ring-pipelined expand/fold schedules == barrier schedule == oracle.

Two layers of checks on an 8-host-device mesh:

* traversal-state parity — σ, d, δ of a forward+backward pass through
  the distributed operators under ``overlap="expand"`` /
  ``"expand+fold"`` must match the single-device dense reference (and
  therefore the barrier schedule, which test_operators.py already pins
  to the same reference) for every distributed engine kind on 2x4 and
  4x2 grids;
* end-to-end parity — ``distributed_betweenness_centrality`` under the
  ring schedules matches ``brandes_reference`` within 1e-6;
* HLO structure — the pipelined lowering contains ring
  ``collective-permute`` steps and *no* monolithic frontier
  ``all-gather`` (and no ``reduce-scatter`` under "expand+fold"), while
  the barrier lowering keeps the all-gather.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import brandes_reference, engine
from repro.core.distributed import (
    distributed_betweenness_centrality,
    make_distributed_round_fn,
)
from repro.core.operators import (
    DenseOperator,
    DistributedOperator,
    DistributedPallasOperator,
    normalize_overlap,
)
from repro.core.scheduler import build_schedule
from repro.graphs import gnp_graph, road_like_graph
from repro.graphs.partition import partition_2d
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host devices"
)

S = 8  # sources per batch
OVERLAPS = ["expand", "expand+fold"]
ENGINE_KINDS = ["sparse", "pallas", "pallas_bf16"]


def _dense_state(graph):
    """(σ, d, δ) of the single-device dense reference operator."""
    n = graph.n
    op = DenseOperator(jnp.asarray(graph.dense_adjacency(np.float32)))
    sources = jnp.arange(min(S, n), dtype=jnp.int32)
    onehot = (jnp.arange(n)[:, None] == sources[None, :]).astype(jnp.float32)
    rng = np.random.default_rng(7)
    omega = jnp.asarray(rng.integers(0, 3, n), jnp.float32)
    fwd = engine.forward_counting(op, onehot)
    delta = engine.backward_accumulation(op, fwd.sigma, fwd.depth, omega, fwd.max_depth)
    return np.asarray(fwd.sigma), np.asarray(fwd.depth), np.asarray(delta)


def _ring_state(graph, engine_kind, overlap, R, C):
    """Same traversal through the ring-scheduled 2-D operators."""
    mesh = make_mesh((R, C), ("data", "model"))
    part = partition_2d(graph, R, C)
    chunk, n_pad = part.chunk, part.n_pad
    rng = np.random.default_rng(7)
    omega_pad = np.zeros(n_pad, np.float32)
    omega_pad[: graph.n] = rng.integers(0, 3, graph.n)
    sources = jnp.arange(min(S, graph.n), dtype=jnp.int32)

    def run(op, omega, srcs):
        row_ids = op.row_ids()
        onehot = (
            (row_ids[:, None] == srcs[None, :]) & (srcs[None, :] >= 0)
        ).astype(jnp.float32)
        fwd = engine.forward_counting(op, onehot)
        delta = engine.backward_accumulation(
            op, fwd.sigma, fwd.depth, omega, fwd.max_depth
        )
        return fwd.sigma, fwd.depth, delta

    if engine_kind == "sparse":
        ring_src, ring_dst = part.ring_arcs()

        def body(rs, rd, omega, srcs):
            op = DistributedOperator(
                None,
                None,
                chunk=chunk,
                R=R,
                C=C,
                row_axis="data",
                col_axis="model",
                overlap=overlap,
                ring_src_local=rs[0, 0],
                ring_dst_local=rd[0, 0],
            )
            return run(op, omega, srcs)

        graph_args = (jnp.asarray(ring_src), jnp.asarray(ring_dst))
        graph_specs = (P("data", "model", None, None), P("data", "model", None, None))
    else:

        def body(blocks, omega, srcs):
            op = DistributedPallasOperator(
                blocks[0, 0],
                chunk=chunk,
                R=R,
                C=C,
                row_axis="data",
                col_axis="model",
                interpret=True,
                overlap=overlap,
            )
            return run(op, omega, srcs)

        dt = jnp.bfloat16 if engine_kind == "pallas_bf16" else jnp.float32
        graph_args = (jnp.asarray(part.dense_blocks(np.float32), dt),)
        graph_specs = (P("data", "model", None, None),)

    owner = P(("model", "data"), None)  # chunk layout == identity vertex order
    fn = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=graph_specs + (P(("model", "data")), P()),
            out_specs=(owner, owner, owner),
            check_vma=False,
        )
    )
    sigma, depth, delta = fn(*graph_args, jnp.asarray(omega_pad), sources)
    n = graph.n
    return np.asarray(sigma)[:n], np.asarray(depth)[:n], np.asarray(delta)[:n]


@pytest.mark.parametrize("grid", [(2, 4), (4, 2)])
@pytest.mark.parametrize("overlap", OVERLAPS)
@pytest.mark.parametrize("engine_kind", ENGINE_KINDS)
def test_ring_operator_state_parity(engine_kind, overlap, grid):
    graph = gnp_graph(26, 0.15, seed=0)
    want = _dense_state(graph)
    got = _ring_state(graph, engine_kind, overlap, *grid)
    np.testing.assert_array_equal(got[1], want[1])  # depth: exact
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)  # σ: integer-valued
    np.testing.assert_allclose(got[2], want[2], rtol=1e-5, atol=1e-6)  # δ


@pytest.mark.parametrize("grid", [(2, 4), (4, 2)])
@pytest.mark.parametrize("engine_kind", ENGINE_KINDS)
def test_ring_end_to_end_matches_oracle(engine_kind, grid):
    g = gnp_graph(26, 0.15, seed=0)
    mesh = make_mesh(grid, ("data", "model"))
    expected = brandes_reference(g)
    bc_none, _ = distributed_betweenness_centrality(
        g, mesh, heuristics="h3", batch_size=8, engine_kind=engine_kind
    )
    bc_ring, _ = distributed_betweenness_centrality(
        g,
        mesh,
        heuristics="h3",
        batch_size=8,
        engine_kind=engine_kind,
        overlap="expand+fold",
    )
    np.testing.assert_allclose(bc_ring, expected, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(bc_ring, bc_none, rtol=1e-6, atol=1e-6)


def test_ring_expand_only_matches_oracle():
    g = road_like_graph(4, 4, spur_fraction=0.6, seed=2)
    mesh = make_mesh((2, 4), ("data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g, mesh, heuristics="h3", overlap="expand"
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-6, atol=1e-6)


def test_ring_subcluster_replicas():
    g = gnp_graph(25, 0.15, seed=2)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g, mesh, replica_axis="pod", heuristics="h1", overlap="expand+fold"
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("overlap", OVERLAPS)
def test_ring_subcluster_divergent_depths(overlap):
    """Replicas whose rounds traverse very different depths (a 41-level
    path round paired with a 2-level G(n,p) round) must not deadlock.

    ppermute ring hops are mesh-wide collective-permutes, so replicas
    with data-dependent level-loop trip counts would arrive at different
    hop instructions and hang the rendezvous; the operators' sync_axes
    loop-bound agreement pins all replicas to max-over-replicas levels
    (regression test for the deadlock the distributed example hit).
    """
    from repro.graphs import disjoint_union, path_graph

    g = disjoint_union(path_graph(40), gnp_graph(16, 0.3, seed=4))
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    bc, _ = distributed_betweenness_centrality(
        g, mesh, replica_axis="pod", batch_size=8, overlap=overlap
    )
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-6, atol=1e-6)


# ------------------------------------------------------- HLO structure
def _lowered_text(part, mesh, schedule, engine_kind, overlap):
    fn = make_distributed_round_fn(
        part, mesh, num_levels=12, engine_kind=engine_kind, overlap=overlap
    )
    if engine_kind == "sparse":
        if overlap == "none":
            gargs = (part.src_local, part.dst_local)
        else:
            gargs = part.ring_arcs()
        specs = tuple(jax.ShapeDtypeStruct(a.shape, jnp.int32) for a in gargs)
    else:
        blocks = part.dense_blocks(np.float32)
        specs = (jax.ShapeDtypeStruct(blocks.shape, jnp.float32),)
    s, k = schedule.batch_size, schedule.derived_per_round
    return fn.lower(
        *specs,
        jax.ShapeDtypeStruct((part.n_pad,), jnp.float32),
        jax.ShapeDtypeStruct((1, s), jnp.int32),
        jax.ShapeDtypeStruct((1, k, 3), jnp.int32),
    ).compile().as_text()


def _sites(text, cls):
    return len(re.findall(rf"\b{cls}\b", text))


@pytest.mark.parametrize("engine_kind", ["sparse", "pallas"])
def test_pipelined_hlo_has_ring_permutes_no_all_gather(engine_kind):
    g = gnp_graph(26, 0.15, seed=0)
    schedule, _, residual, _ = build_schedule(g, batch_size=8)
    part = partition_2d(residual, 2, 4)
    mesh = make_mesh((2, 4), ("data", "model"))

    barrier = _lowered_text(part, mesh, schedule, engine_kind, "none")
    assert _sites(barrier, "all-gather") > 0  # sanity: barrier gathers
    assert _sites(barrier, "collective-permute") == 0

    expand = _lowered_text(part, mesh, schedule, engine_kind, "expand")
    assert _sites(expand, "all-gather") == 0
    assert _sites(expand, "collective-permute") > 0
    assert _sites(expand, "reduce-scatter") > 0  # fold still a barrier

    full = _lowered_text(part, mesh, schedule, engine_kind, "expand+fold")
    assert _sites(full, "all-gather") == 0
    assert _sites(full, "reduce-scatter") == 0
    assert _sites(full, "collective-permute") > _sites(expand, "collective-permute")


# ------------------------------------------------------- policy plumbing
def test_overlap_policy_validation():
    with pytest.raises(ValueError):
        normalize_overlap("ring")
    assert normalize_overlap(None) == "none"
    with pytest.raises(ValueError):
        DistributedOperator(
            None,
            None,
            chunk=4,
            R=2,
            C=4,
            row_axis="data",
            col_axis="model",
            overlap="expand",
            split_backward=True,
        )
    g = gnp_graph(16, 0.2, seed=0)
    schedule, _, residual, _ = build_schedule(g, batch_size=8)
    part = partition_2d(residual, 2, 4)
    mesh = make_mesh((2, 4), ("data", "model"))
    with pytest.raises(ValueError):
        make_distributed_round_fn(
            part, mesh, overlap="expand", fuse_backward_payload=False
        )


def test_single_device_rejects_overlap():
    from repro.core import betweenness_centrality

    with pytest.raises(ValueError):
        betweenness_centrality(gnp_graph(10, 0.3, seed=1), overlap="expand")
