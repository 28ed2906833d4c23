"""The comparison that decides ``correct``: the reference against a plain
Brandes, and a whole run on the CPU with the control and with each fault
planted under the timed path."""
import dataclasses
import json
import pathlib
from collections import deque

import numpy as np
import pytest

from bench import cells, check, graphs

ROOT = pathlib.Path(__file__).resolve().parents[2]


def brandes(row_ptr, col, roots):
    """Σ δ_s over ``roots``, float64, one BFS per root."""
    n = row_ptr.size - 1
    total = np.zeros(n)
    for s in roots:
        sigma = np.zeros(n)
        depth = np.full(n, -1)
        sigma[s], depth[s] = 1.0, 0
        order, q = [], deque([s])
        while q:
            v = q.popleft()
            order.append(v)
            for w in col[row_ptr[v]:row_ptr[v + 1]]:
                if depth[w] < 0:
                    depth[w] = depth[v] + 1
                    q.append(w)
                if depth[w] == depth[v] + 1:
                    sigma[w] += sigma[v]
        delta = np.zeros(n)
        for w in reversed(order):
            for v in col[row_ptr[w]:row_ptr[w + 1]]:
                if depth[v] == depth[w] - 1:
                    delta[v] += sigma[v] / sigma[w] * (1.0 + delta[w])
        delta[s] = 0.0
        total += delta
    return total


@pytest.mark.parametrize("spec", [
    {"generator": "rmat", "scale": 7, "edge_factor": 8, "seed": 3, "a": 0.57, "b": 0.19,
     "c": 0.19},
    {"generator": "lattice", "rows": 9, "cols": 7},
])
def test_reference_matches_plain_brandes(spec):
    from bench.reference import Reference

    n, edges = graphs.build_edges(spec)
    row_ptr, col = graphs.csr(n, edges)
    roots = np.random.default_rng(0).choice(np.nonzero(np.diff(row_ptr))[0], 40, replace=False)
    got, levels = Reference(row_ptr, col, batch=16).contributions(roots)
    want = brandes(row_ptr, col, roots)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-9)
    assert len(levels) == 3 and max(levels) >= 2
    hi, _ = Reference(row_ptr, col, batch=16, precision="high").contributions(roots)
    assert np.max(np.abs(hi - want)) > 0  # the control rounds what the reference keeps


def test_csr_drops_loops_and_duplicates():
    row_ptr, col = graphs.csr(4, np.array([[0, 1], [1, 0], [2, 2], [1, 2], [0, 1]]))
    assert row_ptr.tolist() == [0, 1, 3, 4, 4]
    assert col.tolist() == [1, 0, 2, 1]


def test_span_is_a_third_of_the_window_placed_by_the_seed():
    spans = {check.choose_span(3, 20, seed) for seed in range(40)}
    for a, b in spans:
        assert 3 <= a <= b <= 20 and b - a + 1 == 6
    assert len(spans) > 5
    assert check.choose_span(3, 20, 2**31 + 7) == check.choose_span(3, 20, 2**31 + 7)
    assert check.choose_span(5, 5, 1) == (5, 5)


def shrink(config):
    """The exact-stream cell of ``bench/configs/<config>.json`` at SCALE 8
    with 16-root rounds."""
    bench = cells.load_benchmark(ROOT)
    cell = bench.cell("graph500-s15.exact-stream")
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    cfg["graph"] = dict(cfg["graph"], scale=8, edge_factor=8)
    traffic = dict(cell.traffic, batch_size=16, warmup_blocks=2)
    return bench, dataclasses.replace(cell, name=f"{config}.small", chips=cfg["chips"],
                                      config=cfg, traffic=traffic)


@pytest.fixture(scope="module")
def small_cell():
    return shrink("graph500-s15-1chip")


@pytest.fixture(scope="module")
def small_mesh_cell():
    return shrink("graph500-s16-2x2")


def run(small_cell, mode):
    import jax

    from bench import run as bench_run

    bench, cell = small_cell
    devices = jax.devices()
    assert len(devices) >= cell.chips, "bench/tests/conftest.py sets 8 host devices"
    return bench_run.run_cell(
        bench, cell, seed=2**31 + 3, seconds=0.0, trace=False,
        devices=devices[:cell.chips], peaks=bench_run.load_peaks(), mode=mode,
        compile_cache=False,
    )


def test_sound_run_is_correct(small_cell):
    out = run(small_cell, "program")
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"mteps", "setup_s"}
    assert list(out)[-2:] == ["check", "_lines"]


@pytest.mark.parametrize("mode", ["high", "unchanged", "half", "altered", "rescale", "short"])
def test_control_and_faults_are_not_correct(small_cell, mode):
    out = run(small_cell, mode)
    assert not out["correct"], (mode, out["check"])
    assert any(v["value"] > v["limit"] for v in out["check"].values())


def test_sound_run_on_a_2x2_mesh_is_correct(small_mesh_cell):
    out = run(small_mesh_cell, "program")
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("mode", ["high", "exchange", "unchanged", "half", "altered"])
def test_control_and_faults_on_a_2x2_mesh_are_not_correct(small_mesh_cell, mode):
    out = run(small_mesh_cell, mode)
    assert not out["correct"], (mode, out["check"])
    assert any(v["value"] > v["limit"] for v in out["check"].values())


def test_traced_run_reads_its_metrics(small_cell):
    import jax

    from bench import run as bench_run

    bench, cell = small_cell
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, trace_seconds=0.0))
    out = bench_run.run_cell(
        bench, cell, seed=2**31 + 4, seconds=0.0, trace=True,
        devices=jax.devices()[:1], peaks=bench_run.load_peaks(), compile_cache=False,
    )
    assert out["correct"]
    # the CPU trace has no TPU plane: device readers find nothing to read
    assert set(out["metrics"]) == {"setup.compile_s"}
    assert out["metrics"]["setup.compile_s"]["value"] > 0
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
