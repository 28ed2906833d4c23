"""HLO cost parser validated against closed-form matmul/scan costs."""
import pytest

import jax
import jax.numpy as jnp

from repro.roofline.hlo import analyze_hlo_module
from repro.roofline.model import (
    V5E,
    link_bytes,
    overlap_step_time,
    ring_latency_s,
    ring_steps,
    roofline_terms,
)


def _compile(fn, *specs, in_shardings=None):
    j = jax.jit(fn) if in_shardings is None else jax.jit(fn, in_shardings=in_shardings)
    return j.lower(*specs).compile()


def test_plain_matmul_flops():
    m = k = n = 512
    c = _compile(
        lambda a, b: a @ b,
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32),
    )
    terms = analyze_hlo_module(c.as_text())
    expected = 2.0 * m * k * n
    assert abs(terms["flops"] - expected) / expected < 0.05, terms["flops"]
    # bytes at least inputs+outputs
    assert terms["bytes"] >= 3 * m * n * 4


def test_scan_multiplies_trip_count():
    L, m, k = 8, 128, 128

    def f(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None

        x, _ = jax.lax.scan(body, x, ws)
        return x

    c = _compile(
        f,
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((L, k, k), jnp.float32),
    )
    terms = analyze_hlo_module(c.as_text())
    expected = 2.0 * m * k * k * L
    assert abs(terms["flops"] - expected) / expected < 0.05, terms["flops"]
    assert terms["unknown_trip_whiles"] == 0


def test_collectives_counted_with_groups():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    m = k = n = 256

    def f(a, b):
        return a @ b

    c = (
        jax.jit(
            f,
            in_shardings=(
                NamedSharding(mesh, P("data", "model")),
                NamedSharding(mesh, P("model", None)),
            ),
            out_shardings=NamedSharding(mesh, P("data", None)),
        )
        .lower(
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
        )
        .compile()
    )
    terms = analyze_hlo_module(c.as_text())
    # contraction over the sharded k axis must produce a cross-"model"
    # reduction (all-reduce or reduce-scatter) over groups of 4
    colls = terms["collectives"]
    assert colls, c.as_text()[:2000]
    assert any(r["group_size"] == 4 for r in colls)
    assert link_bytes(colls) > 0


def test_roofline_terms_shape():
    hlo_terms = {
        "flops": 197e12,
        "bytes": 819e9,
        "collectives": [
            {"class": "all-reduce", "group_size": 4, "operand_bytes": 50e9}
        ],
        "collective_operand_bytes": {"all-reduce": 50e9},
        "unknown_trip_whiles": 0,
    }
    t = roofline_terms(hlo_terms, n_devices=256, model_flops_total=197e12 * 256)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 1.0) < 1e-9
    assert abs(t.collective_s - 1.5) < 1e-9  # 2*(4-1)/4 * 50e9 / 50e9
    assert t.bottleneck == "collective"
    assert abs(t.useful_fraction - 1.0) < 1e-9
    assert t.ring_steps == 6  # all-reduce over g=4: 2*(g-1) hops
    assert abs(t.ring_latency_s - 6 * V5E.ici_step_latency_s) < 1e-15


def test_ring_step_counts_by_class():
    recs = [
        {"class": "all-gather", "group_size": 4, "operand_bytes": 1.0},
        {"class": "reduce-scatter", "group_size": 4, "operand_bytes": 1.0},
        {"class": "all-reduce", "group_size": 8, "operand_bytes": 1.0},
        {"class": "collective-permute", "group_size": 4, "operand_bytes": 1.0},
    ]
    # (4-1) + (4-1) + 2*(8-1) + 1
    assert ring_steps(recs) == 3 + 3 + 14 + 1
    assert abs(ring_latency_s(recs) - 21 * V5E.ici_step_latency_s) < 1e-15


def test_overlap_step_time_model():
    # barrier (k=1) is strictly additive
    assert abs(overlap_step_time(3.0, 1.0, 1) - 4.0) < 1e-12
    # deep ring exposes only the dominant term (+ one slice of the minor)
    assert abs(overlap_step_time(3.0, 1.0, 4) - (3.0 + 0.25)) < 1e-12
    assert abs(overlap_step_time(1.0, 3.0, 4) - (3.0 + 0.25)) < 1e-12
    # pipelining never loses to the barrier schedule
    for k in (2, 4, 16):
        assert overlap_step_time(2.0, 2.0, k) <= 4.0


def test_ring_lowering_counted_by_parser():
    """A hand-rolled ppermute ring round-trips through the HLO parser."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("data",))
    perm = [(s, (s + 1) % 8) for s in range(8)]

    def body(x):
        acc = x
        for _ in range(7):
            x = jax.lax.ppermute(x, "data", perm)
            acc = acc + x
        return acc

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False
        )
    )
    text = fn.lower(jax.ShapeDtypeStruct((64, 16), jnp.float32)).compile().as_text()
    terms = analyze_hlo_module(text)
    permutes = [
        r for r in terms["collectives"] if r["class"] == "collective-permute"
    ]
    assert permutes, text[:2000]
    assert ring_steps(permutes) >= 7


@pytest.mark.parametrize(
    "platform,kind,want",
    [
        ("cpu", "cpu", "tpu-v5e"),  # CPU rehearsals plan as a v5e chip
        ("tpu", "TPU v5 lite", "tpu-v5e"),
        ("tpu", "TPU v9 imaginary", None),  # an unlisted chip is an error
        ("gpu", "NVIDIA H100", None),
    ],
)
def test_hardware_peaks_keyed_by_device_kind(platform, kind, want):
    import types

    from repro.roofline.model import HARDWARE, device_hardware

    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    if want is None:
        with pytest.raises(ValueError, match="no peak-rate entry"):
            device_hardware(dev)
    else:
        assert device_hardware(dev).name == want
    assert device_hardware().name == "tpu-v5e"  # the test backend is the CPU
    assert all(spec.peak_bf16_flops > 0 for spec in HARDWARE.values())
