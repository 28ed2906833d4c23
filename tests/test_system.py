"""End-to-end behaviour tests for the paper's system.

These exercise the *whole* stack the way a user would: launchers,
schedule bookkeeping, fault-injection recovery, and the BC-round ledger —
complementing the unit/oracle tests elsewhere.
"""
import numpy as np
import pytest

import jax

from repro.core import betweenness_centrality, brandes_reference
from repro.core.scheduler import build_schedule
from repro.distributed.fault_tolerance import RoundLedger
from repro.graphs import gnp_graph


def test_bc_resumes_from_partial_rounds():
    """Kill-and-resume: accumulating only uncommitted rounds (the round
    ledger protocol) gives the exact same scores as an unbroken run."""
    g = gnp_graph(30, 0.15, seed=11)
    full = betweenness_centrality(g, batch_size=4, heuristics="h3")

    # simulate: run rounds one at a time, "crash" halfway, resume via ledger
    from repro.core.bc import make_round_fn
    from repro.core import engine
    import jax.numpy as jnp

    schedule, prep, residual, omega_i = build_schedule(
        g, batch_size=4, heuristics="h3"
    )
    adjacency = jnp.asarray(residual.dense_adjacency(np.float32))
    round_fn = jax.jit(
        make_round_fn(lambda: engine.make_dense_operator(adjacency), g.n)
    )
    omega = jnp.asarray(omega_i, jnp.float32)

    def run_rounds(ledger, bc, ns_by_root, round_ids):
        for rid in round_ids:
            if not ledger.try_commit(rid):
                continue  # duplicate completion (speculative re-execution)
            rnd = schedule.rounds[rid]
            bc_r, ns, roots, _levels = round_fn(
                jnp.asarray(rnd.sources), jnp.asarray(rnd.derived), omega
            )
            bc += np.asarray(bc_r, np.float64)
            for r, nv in zip(np.asarray(roots), np.asarray(ns, np.float64)):
                if r >= 0:
                    ns_by_root[int(r)] = float(nv)
        return bc

    n_rounds = len(schedule.rounds)
    ledger = RoundLedger()
    bc = np.zeros(g.n, np.float64)
    ns_by_root: dict[int, float] = {}
    # first "process" dies after half the rounds
    bc = run_rounds(ledger, bc, ns_by_root, range(n_rounds // 2))
    # resume from persisted ledger state; re-issue EVERYTHING (duplicates
    # must be dropped), plus a speculative duplicate of round 0
    ledger2 = RoundLedger.from_state(ledger.state())
    bc = run_rounds(ledger2, bc, ns_by_root, [0] + list(range(n_rounds)))

    from repro.core.heuristics.one_degree import leaf_correction

    omega_np = omega_i.astype(np.float64)
    for v, nv in ns_by_root.items():
        if omega_np[v] > 0:
            bc[v] += leaf_correction(omega_np[v], nv)
    for v, n_comp in schedule.analytic_corrections:
        bc[int(v)] += leaf_correction(omega_np[int(v)], float(n_comp))

    np.testing.assert_allclose(bc, full.bc, rtol=1e-6)
    np.testing.assert_allclose(bc, brandes_reference(g), rtol=1e-5, atol=1e-5)


def test_bc_driver_checkpoint_kill_and_resume(tmp_path):
    """A run killed mid-loop leaves a consistent BCCheckpoint; a fresh
    driver resumes from it and reproduces the unbroken result exactly."""
    import jax.numpy as jnp

    from repro.core import engine
    from repro.core.bc import make_round_fn
    from repro.core.driver import BCDriver
    from repro.distributed.fault_tolerance import BCCheckpoint

    g = gnp_graph(30, 0.15, seed=13)
    full = betweenness_centrality(g, batch_size=4, heuristics="h3")

    schedule, prep, residual, omega_i = build_schedule(g, batch_size=4, heuristics="h3")
    adjacency = jnp.asarray(residual.dense_adjacency(np.float32))
    omega = jnp.asarray(omega_i, jnp.float32)
    base_fn = jax.jit(
        make_round_fn(lambda: engine.make_dense_operator(adjacency), g.n)
    )

    class Crash(RuntimeError):
        pass

    def crashing_round_fn(limit):
        calls = {"n": 0}

        def fn(sources, derived):
            calls["n"] += 1
            if calls["n"] > limit:
                raise Crash
            bc_r, ns, roots, levels = base_fn(sources[0], derived[0], omega)
            return bc_r, ns[None], roots[None], levels[None]

        return fn

    ckpt = BCCheckpoint(str(tmp_path / "bc.npz"))
    n_rounds = len(schedule.rounds)
    assert n_rounds >= 4
    with pytest.raises(Crash):
        BCDriver(
            crashing_round_fn(n_rounds // 2),
            schedule,
            n=g.n,
            prep=prep,
            checkpoint=ckpt,
            checkpoint_every=1,
        ).run()
    assert ckpt.exists()
    _, _, committed = ckpt.load()
    assert 0 < len(committed) < n_rounds

    # resume: only the uncommitted tail is re-dealt
    resumed = BCDriver(
        crashing_round_fn(10**9),
        schedule,
        n=g.n,
        prep=prep,
        checkpoint=ckpt,
        checkpoint_every=1,
    ).run()
    assert resumed.rounds_run == n_rounds - len(committed)
    np.testing.assert_allclose(resumed.bc, full.bc, rtol=1e-6)
    np.testing.assert_allclose(resumed.bc, brandes_reference(g), rtol=1e-5, atol=1e-5)
    # a third run is a no-op that still reproduces the full scores
    third = BCDriver(
        crashing_round_fn(0), schedule, n=g.n, prep=prep, checkpoint=ckpt
    ).run()
    assert third.rounds_run == 0
    np.testing.assert_allclose(third.bc, full.bc, rtol=1e-6)

    # resuming against a different schedule must refuse, not mix sums
    other_schedule, other_prep, _, _ = build_schedule(g, batch_size=8, heuristics="h3")
    with pytest.raises(ValueError, match="different"):
        BCDriver(
            crashing_round_fn(0),
            other_schedule,
            n=g.n,
            prep=other_prep,
            checkpoint=ckpt,
        )


@pytest.fixture
def compile_cache_config():
    """Restore JAX's persistent-cache settings after a test changes them."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
        )
    }
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, monkeypatch, compile_cache_config, from_env):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, and only
    there; unset, to the checkout's fixed .jax-cache."""
    import pathlib

    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
        want = str(tmp_path / "cc")
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR == pathlib.Path(__file__).resolve().parents[1] / ".jax-cache"
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # small programs are stored too: the kernels must be found again
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    if from_env:
        jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
        assert any((tmp_path / "cc").iterdir())


def test_bc_launcher_cli(tmp_path, monkeypatch, compile_cache_config):
    import sys
    from repro.launch import bc as bc_cli

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax-cache"))
    out = tmp_path / "scores.npy"
    argv = sys.argv
    sys.argv = [
        "bc", "--grid", "6x6", "--heuristics", "h3", "--out", str(out),
    ]
    try:
        bc_cli.main()
    finally:
        sys.argv = argv
    scores = np.load(str(out))
    from repro.graphs import grid_graph

    np.testing.assert_allclose(
        scores, brandes_reference(grid_graph(6, 6)), rtol=1e-5, atol=1e-5
    )


def test_training_loss_decreases():
    from repro.configs.registry import get_arch
    from repro.launch.train import reduced_lm, train_lm

    cfg = reduced_lm(get_arch("gemma-7b").arch, layers=2, d_model=128, vocab=512)
    out = train_lm(cfg, steps=25, batch=4, seq=96)
    first = np.mean(out["losses"][:5])
    last = np.mean(out["losses"][-5:])
    assert last < first, (first, last)


def test_serve_loop_runs():
    from repro.configs.registry import get_arch
    from repro.launch.serve import serve_loop
    from repro.launch.train import reduced_lm

    cfg = reduced_lm(get_arch("codeqwen1.5-7b").arch, 2, 128, 512)
    out, t_p, t_d = serve_loop(cfg, batch=2, prompt_len=8, gen=4)
    assert out.shape == (2, 4)
    assert (out >= 0).all() and (out < cfg.vocab).all()
