"""Operation and byte counts of the SpMM kernels, worked by hand."""
import importlib.util
import pathlib

import numpy as np
import pytest

from bench import roofline

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_forward_cost_rmat_scale15():
    # 882,378 arcs, 32,768 rows, 128 roots
    c = roofline.forward_cost(882_378, 32_768, 128)
    assert c.ops == 2 * 882_378 * 128 == 225_888_768
    # indices 3,529,512 B + σ, depth read + product written: 3 × 16,777,216 B
    assert c.bytes == 3_529_512 + 50_331_648 == 53_861_160
    t, bound = roofline.least_seconds(c, V5E)
    assert bound == "memory" and t == pytest.approx(53_861_160 / 819e9)


def test_backward_cost_adds_delta_and_omega():
    c = roofline.backward_cost(10, 4, 2)
    # 10 indices + (δ, σ, depth read, product written) 4 × 4 × 2 + ω 4, in words
    assert c.bytes == 4 * (10 + 32 + 4) == 184
    assert c.ops == 40


def test_costs_of_a_2x4_block():
    # an R x C = 2 x 4 block of n = 64: rows n / R = 32, cols n / C = 16
    f = roofline.forward_cost(100, 32, 8, cols=16)
    assert f.bytes == 4 * (100 + 2 * 16 * 8 + 32 * 8) and f.ops == 2 * 100 * 8
    b = roofline.backward_cost(100, 32, 8, cols=16)
    assert b.bytes == 4 * (100 + 3 * 16 * 8 + 16 + 32 * 8)


def test_compute_bound_when_arcs_dominate():
    t, bound = roofline.least_seconds(roofline.KernelCost(ops=1e15, bytes=1.0), V5E)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


def summary(fwd_calls, bwd_calls, fwd_ns, bwd_ns, busy_ns, window_ns):
    chip = {"forward_calls": fwd_calls, "backward_calls": bwd_calls, "forward_ns": fwd_ns,
            "backward_ns": bwd_ns, "busy_ns": busy_ns, "collective_ns": 0,
            "collective_exposed_ns": 0}
    return {"window_ns": window_ns, "chips": {"TPU:0": chip}}


def test_roofline_reader_matches_hand_count():
    ctx = {"summary": summary(7, 5, 24_000_000 * 7, 78_000_000 * 5, 560_000_000, 570_000_000),
           "peaks": V5E, "config": {"mesh": [1, 1], "overlap": "none"},
           "col": np.zeros(882_378),
           "n": 32_768, "batch_size": 128}
    fwd = 53_861_160 / 819e9
    bwd = 4 * (882_378 + 4 * 32_768 * 128 + 32_768) / 819e9
    want = 100 * (7 * fwd + 5 * bwd) / (0.168 + 0.390)
    assert reader("kernel.spmm_roofline")(ctx) == pytest.approx(want)
    assert reader("kernel.spmm_busy_share")(ctx) == pytest.approx(100 * 0.558 / 0.56)
    assert reader("device.idle_share")(ctx) == pytest.approx(100 * (1 - 56 / 57))


def test_ring_roofline_counts_a_level_once_over_its_ring_steps():
    # a 2x2 mesh on a ring: each level calls each kernel twice per chip
    chips = {}
    for k in range(4):
        chips[f"TPU:{k}"] = {"forward_calls": 6, "backward_calls": 4, "forward_ns": 60_000_000,
                             "backward_ns": 80_000_000, "busy_ns": 150_000_000,
                             "collective_ns": 20_000_000, "collective_exposed_ns": 5_000_000}
    ctx = {"summary": {"window_ns": 200_000_000, "chips": chips}, "peaks": V5E,
           "config": {"mesh": [2, 2], "overlap": "expand+fold"}, "col": np.zeros(4_000),
           "n": 1_024, "batch_size": 128}
    arcs, half = 1_000, 512
    fwd = 4 * (arcs + 2 * half * 128 + half * 128) / 819e9
    bwd = 4 * (arcs + 3 * half * 128 + half + half * 128) / 819e9
    want = 100 * 4 * (3 * fwd + 2 * bwd) / (4 * 0.14)
    assert reader("kernel.spmm_roofline")(ctx) == pytest.approx(want)
    assert reader("collective.exposed_share")(ctx) == pytest.approx(2.5)


def test_readers_return_nothing_without_a_trace_or_kernels():
    ctx = {"summary": None, "peaks": V5E}
    for name in ("kernel.spmm_roofline", "kernel.spmm_busy_share", "device.idle_share",
                 "collective.exposed_share"):
        assert reader(name)(ctx) is None
    ctx = {"summary": summary(0, 0, 0, 0, 10, 20), "peaks": V5E,
           "config": {"mesh": [1, 1], "overlap": "none"},
           "col": np.zeros(4), "n": 4, "batch_size": 1}
    assert reader("kernel.spmm_roofline")(ctx) is None
    assert reader("kernel.spmm_busy_share")(ctx) is None
    assert reader("collective.exposed_share")(ctx) is None  # one chip: no collective
