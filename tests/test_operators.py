"""Operator-seam parity: every TraversalOperator implementation must
produce bit-identical level structure (d), path counts (σ) and — up to
f32 summation order — dependencies (δ) on the same graphs.

This checks the unified engine at the operator protocol boundary rather
than only end-to-end: forward_counting / backward_accumulation are run
directly against each operator and the raw traversal state is compared.
The distributed operators run inside a shard_map harness whose out_specs
reassemble the owner-sharded chunks into global arrays (the chunk layout
is identity in vertex order — graphs/partition.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import engine
from repro.core.operators import (
    DenseOperator,
    DistributedOperator,
    DistributedPallasOperator,
    PallasDenseOperator,
    SparseOperator,
)
from repro.graphs import cycle_graph, gnp_graph, road_like_graph
from repro.graphs.partition import partition_2d

GRAPHS = {
    "gnp26": lambda: gnp_graph(26, 0.15, seed=0),
    "gnp23": lambda: gnp_graph(23, 0.2, seed=1),
    "cycle17": lambda: cycle_graph(17),
    "road4x4": lambda: road_like_graph(4, 4, spur_fraction=0.5, seed=2),
}

S = 8  # sources per batch


def _single_device_state(graph, operator, num_levels=None):
    """(σ, d, δ) of one forward+backward pass against ``operator``."""
    n = graph.n
    sources = jnp.arange(min(S, n), dtype=jnp.int32)
    onehot = (jnp.arange(n)[:, None] == sources[None, :]).astype(jnp.float32)
    rng = np.random.default_rng(7)
    omega = jnp.asarray(rng.integers(0, 3, n), jnp.float32)

    fwd = engine.forward_counting(operator, onehot, num_levels=num_levels)
    delta = engine.backward_accumulation(
        operator, fwd.sigma, fwd.depth, omega, fwd.max_depth, num_levels=num_levels
    )
    return np.asarray(fwd.sigma), np.asarray(fwd.depth), np.asarray(delta)


def _make_operator(kind, graph):
    n = graph.n
    if kind == "dense":
        return DenseOperator(jnp.asarray(graph.dense_adjacency(np.float32)))
    if kind == "sparse":
        src_p, dst_p, _ = graph.padded_arcs(multiple=8)
        return SparseOperator(jnp.asarray(src_p), jnp.asarray(dst_p), n)
    if kind == "pallas":
        return PallasDenseOperator(
            jnp.asarray(graph.dense_adjacency(np.float32)), interpret=True
        )
    if kind == "pallas_bf16":
        return PallasDenseOperator(
            jnp.asarray(graph.dense_adjacency(np.float32), jnp.bfloat16),
            interpret=True,
        )
    raise ValueError(kind)


@pytest.mark.parametrize("graph_name", sorted(GRAPHS))
@pytest.mark.parametrize("kind", ["sparse", "pallas", "pallas_bf16"])
def test_single_device_operator_parity(graph_name, kind):
    graph = GRAPHS[graph_name]()
    want = _single_device_state(graph, _make_operator("dense", graph))
    got = _single_device_state(graph, _make_operator(kind, graph))
    np.testing.assert_array_equal(got[1], want[1])  # depth: exact
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)  # σ: integer-valued
    np.testing.assert_allclose(got[2], want[2], rtol=1e-5, atol=1e-6)  # δ


@pytest.mark.parametrize("graph_name", sorted(GRAPHS))
def test_static_num_levels_operator_parity(graph_name):
    graph = GRAPHS[graph_name]()
    want = _single_device_state(graph, _make_operator("dense", graph))
    got = _single_device_state(
        graph, _make_operator("dense", graph), num_levels=graph.n + 1
    )
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_allclose(got[2], want[2], rtol=1e-5, atol=1e-6)


# ------------------------------------------------- distributed operators
needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host devices"
)


def _distributed_state(graph, engine_kind, R=2, C=4):
    """Same traversal through the 2-D operators, reassembled to global."""
    from repro.launch.mesh import make_mesh

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

        def body(src_local, dst_local, omega, srcs):
            op = DistributedOperator(
                src_local[0, 0],
                dst_local[0, 0],
                chunk=chunk,
                R=R,
                C=C,
                row_axis="data",
                col_axis="model",
            )
            return run(op, omega, srcs)

        graph_args = (jnp.asarray(part.src_local), jnp.asarray(part.dst_local))
        graph_specs = (P("data", "model", None), P("data", "model", None))
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


@needs_mesh
@pytest.mark.parametrize("graph_name", sorted(GRAPHS))
@pytest.mark.parametrize("engine_kind", ["sparse", "pallas", "pallas_bf16"])
def test_distributed_operator_parity(graph_name, engine_kind):
    graph = GRAPHS[graph_name]()
    want = _single_device_state(graph, _make_operator("dense", graph))
    got = _distributed_state(graph, engine_kind)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_allclose(got[2], want[2], rtol=1e-5, atol=1e-6)
