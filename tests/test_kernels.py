"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.graphs import gnp_graph
from repro.kernels import ops, ref


def _bc_state(n, s, seed, lvl):
    """A plausible mid-traversal BC state for a random graph."""
    rng = np.random.default_rng(seed)
    g = gnp_graph(n, min(0.3, 8.0 / n), seed=seed)
    A = g.dense_adjacency(np.float32)
    sigma = rng.integers(0, 5, size=(n, s)).astype(np.float32)
    depth = rng.integers(-1, lvl + 3, size=(n, s)).astype(np.int32)
    sigma = np.where(depth >= 0, np.maximum(sigma, 1.0), 0.0)
    delta = rng.random((n, s)).astype(np.float32) * (depth >= 0)
    omega = rng.integers(0, 3, size=n).astype(np.float32)
    return A, sigma, depth, delta, omega


SHAPES = [(8, 4), (16, 16), (64, 8), (128, 128), (130, 33), (256, 64)]


@pytest.mark.parametrize("n,s", SHAPES)
@pytest.mark.parametrize("adj_dtype", [jnp.float32, jnp.bfloat16])
def test_frontier_spmm_matches_ref(n, s, adj_dtype):
    lvl = 2
    A, sigma, depth, _, _ = _bc_state(n, s, seed=n + s, lvl=lvl)
    A = jnp.asarray(A, adj_dtype)
    got_s, got_d = ops.frontier_spmm(
        A, jnp.asarray(sigma), jnp.asarray(depth), lvl, interpret=True
    )
    exp_s, exp_d = ref.frontier_spmm_ref(A, jnp.asarray(sigma), jnp.asarray(depth), lvl)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(exp_s), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(exp_d))


@pytest.mark.parametrize("n,s", SHAPES)
@pytest.mark.parametrize("adj_dtype", [jnp.float32, jnp.bfloat16])
def test_dependency_spmm_matches_ref(n, s, adj_dtype):
    lvl = 1
    A, sigma, depth, delta, omega = _bc_state(n, s, seed=2 * n + s, lvl=lvl)
    A = jnp.asarray(A, adj_dtype)
    got = ops.dependency_spmm(
        A,
        jnp.asarray(sigma),
        jnp.asarray(depth),
        jnp.asarray(delta),
        jnp.asarray(omega),
        lvl,
        interpret=True,
    )
    exp = ref.dependency_spmm_ref(
        A,
        jnp.asarray(sigma),
        jnp.asarray(depth),
        jnp.asarray(delta),
        jnp.asarray(omega),
        lvl,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-5, atol=1e-6)


def test_frontier_spmm_full_level_sequence():
    """Kernel levels chained end-to-end reproduce the engine's forward."""
    from repro.core import engine

    g = gnp_graph(48, 0.12, seed=11)
    A = jnp.asarray(g.dense_adjacency(np.float32))
    n, s = 48, 8
    sources = jnp.arange(s, dtype=jnp.int32)
    onehot = (jnp.arange(n)[:, None] == sources[None, :]).astype(jnp.float32)
    want = engine.forward_counting(engine.make_dense_operator(A), onehot)

    sigma = onehot
    depth = jnp.where(onehot > 0, 0, -1).astype(jnp.int32)
    for lvl in range(1, 20):
        sigma, depth = ops.frontier_spmm(A, sigma, depth, lvl, interpret=True)
    np.testing.assert_allclose(np.asarray(sigma), np.asarray(want.sigma), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(depth), np.asarray(want.depth))


# rectangular pre-fold variants feeding the 2-D distributed engine
RECT_SHAPES = [(8, 8, 4), (16, 8, 16), (64, 24, 8), (130, 40, 33)]


@pytest.mark.parametrize("m,k,s", RECT_SHAPES)
@pytest.mark.parametrize("adj_dtype", [jnp.float32, jnp.bfloat16])
def test_frontier_spmm_partial_matches_ref(m, k, s, adj_dtype):
    lvl = 2
    rng = np.random.default_rng(m + k + s)
    A = jnp.asarray((rng.random((m, k)) < 0.3), adj_dtype)
    sigma = jnp.asarray(rng.integers(0, 5, (k, s)), jnp.float32)
    depth = jnp.asarray(rng.integers(-1, lvl + 3, (k, s)), jnp.int32)
    got = ops.frontier_spmm_partial(A, sigma, depth, lvl, interpret=True)
    exp = ref.frontier_partial_ref(A, sigma, depth, lvl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-6)


@pytest.mark.parametrize("m,k,s", RECT_SHAPES)
@pytest.mark.parametrize("adj_dtype", [jnp.float32, jnp.bfloat16])
def test_dependency_spmm_partial_matches_ref(m, k, s, adj_dtype):
    lvl = 1
    rng = np.random.default_rng(2 * m + k + s)
    A = jnp.asarray((rng.random((m, k)) < 0.3), adj_dtype)
    sigma = jnp.asarray(
        np.maximum(rng.integers(0, 5, (k, s)), 1).astype(np.float32)
    )
    depth = jnp.asarray(rng.integers(-1, lvl + 3, (k, s)), jnp.int32)
    delta = jnp.asarray(rng.random((k, s)), jnp.float32)
    omega = jnp.asarray(rng.integers(0, 3, k), jnp.float32)
    got = ops.dependency_spmm_partial(A, sigma, depth, delta, omega, lvl, interpret=True)
    exp = ref.dependency_partial_ref(A, sigma, depth, delta, omega, lvl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("m,k,s", RECT_SHAPES)
@pytest.mark.parametrize("adj_dtype", [jnp.float32, jnp.bfloat16])
def test_frontier_partial_acc_chains_chunks(m, k, s, adj_dtype):
    """Chunked-operand mode: threading ``acc`` over column chunks equals
    one whole-block partial (the ring-pipelined expand contract)."""
    lvl = 2
    rng = np.random.default_rng(3 * m + k + s)
    A = jnp.asarray((rng.random((m, 2 * k)) < 0.3), adj_dtype)
    sigma = jnp.asarray(rng.integers(0, 5, (2 * k, s)), jnp.float32)
    depth = jnp.asarray(rng.integers(-1, lvl + 3, (2 * k, s)), jnp.int32)
    want = ops.frontier_spmm_partial(A, sigma, depth, lvl, interpret=True)
    acc = jnp.zeros((m, s), jnp.float32)
    for c in range(2):
        sl = slice(c * k, (c + 1) * k)
        acc = ops.frontier_spmm_partial(
            A[:, sl], sigma[sl], depth[sl], lvl, acc=acc, interpret=True
        )
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("m,k,s", RECT_SHAPES)
def test_dependency_partial_acc_chains_chunks(m, k, s):
    lvl = 1
    rng = np.random.default_rng(4 * m + k + s)
    A = jnp.asarray((rng.random((m, 2 * k)) < 0.3), jnp.float32)
    sigma = jnp.asarray(np.maximum(rng.integers(0, 5, (2 * k, s)), 1), jnp.float32)
    depth = jnp.asarray(rng.integers(-1, lvl + 3, (2 * k, s)), jnp.int32)
    delta = jnp.asarray(rng.random((2 * k, s)), jnp.float32)
    omega = jnp.asarray(rng.integers(0, 3, 2 * k), jnp.float32)
    want = ops.dependency_spmm_partial(
        A, sigma, depth, delta, omega, lvl, interpret=True
    )
    acc = jnp.zeros((m, s), jnp.float32)
    for c in range(2):
        sl = slice(c * k, (c + 1) * k)
        acc = ops.dependency_spmm_partial(
            A[:, sl], sigma[sl], depth[sl], delta[sl], omega[sl], lvl,
            acc=acc, interpret=True,
        )
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("V,D,B,L", [(32, 8, 4, 3), (64, 128, 8, 5), (128, 96, 16, 10), (1000, 64, 32, 26)])
@pytest.mark.parametrize("table_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("weighted", [False, True])
def test_segment_bag_matches_ref(V, D, B, L, table_dtype, weighted):
    rng = np.random.default_rng(V + D + B + L)
    table = jnp.asarray(rng.standard_normal((V, D)), table_dtype)
    indices = rng.integers(-1, V, size=(B, L)).astype(np.int32)
    weights = (
        jnp.asarray(rng.random((B, L)), jnp.float32) if weighted else None
    )
    got = ops.segment_bag(table, jnp.asarray(indices), weights, interpret=True)
    exp = ref.segment_bag_ref(table, jnp.asarray(indices), weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-2 if table_dtype == jnp.bfloat16 else 1e-6, atol=1e-5)


def test_segment_bag_all_padding_bag():
    table = jnp.ones((16, 8), jnp.float32)
    indices = jnp.full((3, 4), -1, jnp.int32)
    out = ops.segment_bag(table, indices, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 0.0)


@pytest.mark.parametrize("engine_kind", ["pallas", "pallas_bf16"])
def test_bc_end_to_end_with_pallas_engine(engine_kind):
    """Full BC through the fused-kernel engine (interpret mode) == oracle."""
    from repro.core import betweenness_centrality, brandes_reference

    g = gnp_graph(20, 0.18, seed=21)
    got = betweenness_centrality(
        g, batch_size=8, heuristics="h3", engine_kind=engine_kind
    )
    np.testing.assert_allclose(got.bc, brandes_reference(g), rtol=1e-5, atol=1e-5)


# ------------------------------------------------- precision + dispatch
def _dot_precisions(jaxpr):
    """``precision`` of every dot_general in a jaxpr, kernel bodies and
    nested jits included."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(sub, ClosedJaxpr):
                    found += _dot_precisions(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    found += _dot_precisions(sub)
    return found


def _traversal_contractions():
    """(name, fn, args) for every A @ x contraction of the traversal:
    the Pallas kernels (traced, not run), their jnp references and the
    operators' XLA dots."""
    from repro.core.operators import (
        DenseOperator,
        DistributedPallasOperator,
        DistributedPallasSparseOperator,
        DistributedWeightedDenseOperator,
        PallasDenseOperator,
        WeightedDenseOperator,
    )

    n, s = 256, 128
    a = jnp.zeros((n, n), jnp.float32)
    st = jnp.zeros((n, s), jnp.float32)
    dp = jnp.zeros((n, s), jnp.int32)
    om = jnp.zeros((n,), jnp.float32)
    tiles = jnp.zeros((4, 128, 128), jnp.float32)
    idx = jnp.array([0, 0, 1, 1], jnp.int32), jnp.array([0, 1, 0, 1], jnp.int32)
    geo = dict(chunk=n, R=1, C=1, row_axis="data", col_axis="model")
    kw = dict(interpret=False)
    return [
        ("frontier_spmm", lambda: ops.frontier_spmm(a, st, dp, 1, **kw)),
        ("dependency_spmm", lambda: ops.dependency_spmm(a, st, dp, st, om, 1, **kw)),
        ("frontier_partial", lambda: ops.frontier_spmm_partial(a, st, dp, 1, acc=st, **kw)),
        ("dependency_partial",
         lambda: ops.dependency_spmm_partial(a, st, dp, st, om, 1, acc=st, **kw)),
        ("frontier_sparse",
         lambda: ops.frontier_spmm_sparse(tiles, *idx, st, dp, 1, m=n, **kw)),
        ("dependency_sparse",
         lambda: ops.dependency_spmm_sparse(tiles, *idx, st, dp, st, om, 1, m=n, **kw)),
        ("frontier_ref", lambda: ref.frontier_spmm_ref(a, st, dp, 1)),
        ("dependency_ref", lambda: ref.dependency_spmm_ref(a, st, dp, st, om, 1)),
        ("frontier_partial_ref", lambda: ref.frontier_partial_ref(a, st, dp, 1)),
        ("dependency_partial_ref",
         lambda: ref.dependency_partial_ref(a, st, dp, st, om, 1)),
        ("DenseOperator.apply", lambda: DenseOperator(a).apply(st)),
        ("PallasDenseOperator.apply", lambda: PallasDenseOperator(a).apply(st)),
        ("WeightedDenseOperator.apply", lambda: WeightedDenseOperator(a, 1.0).apply(st)),
        ("WeightedDenseOperator.sigma_step",
         lambda: WeightedDenseOperator(a, 1.0).sigma_step(st, st)),
        ("WeightedDenseOperator.delta_step",
         lambda: WeightedDenseOperator(a, 1.0).delta_step(st, st)),
        ("DistributedPallasOperator._local",
         lambda: DistributedPallasOperator(a, **geo)._local(st)),
        ("DistributedPallasSparseOperator._local",
         lambda: DistributedPallasSparseOperator(tiles, *idx, **geo)._local(st)),
        ("DistributedWeightedDenseOperator._local",
         lambda: DistributedWeightedDenseOperator(a, delta=1.0, **geo)._local(st)),
    ]


@pytest.mark.parametrize(
    "name,fn", _traversal_contractions(), ids=[c[0] for c in _traversal_contractions()]
)
def test_traversal_contractions_pin_f32_precision(name, fn):
    """Every A @ x of the traversal asks for full f32 precision.

    The 0/1 adjacency is exact in bf16 but σ and g = (1+δ+ω)/σ are not;
    the TPU's default precision would round them to bf16 on the MXU.  A
    dot that drops the precision argument fails here, on the CPU.
    """
    import jax

    precisions = _dot_precisions(jax.make_jaxpr(fn)().jaxpr)
    assert precisions, f"{name}: no contraction traced"
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    assert all(p == highest for p in precisions), (name, precisions)


def test_interpret_mode_follows_backend(monkeypatch):
    """Kernels compile on TPU, interpret on the CPU backend, and refuse
    any other backend instead of interpreting there in silence."""
    import jax

    assert ops.resolve_interpret(None) is True  # tests run on the CPU backend
    assert ops.resolve_interpret(False) is False  # an explicit choice stands
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert ops.resolve_interpret(True) is True
    with pytest.raises(RuntimeError, match="TPU programs"):
        ops.resolve_interpret(None)
    # a shape no other test traces, so no cached trace can answer for it
    A, sigma, depth, _, _ = _bc_state(24, 3, 0, 1)
    with pytest.raises(RuntimeError, match="TPU programs"):
        ops.frontier_spmm_partial(A, sigma, depth, 1)
