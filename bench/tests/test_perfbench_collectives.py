"""The four-chip cell and the readers of its collectives layer:
``collective.level_mib`` on hand-built runs and on a real run of the
cell's configuration on a 2x2 mesh of host devices."""
import dataclasses
import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import cells

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "graph500-s16-2x2.exact-stream"
NEW = {"collective.exposed_share", "collective.level_mib"}
#: what a run on a listed chip gives the readers
CHIP = {"peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark(ROOT)


def test_the_four_chip_cell_lists_the_collective_metrics(bench):
    cell = bench.cell(CELL)
    assert cell.chips == 4 and cell.config["mesh"] == [2, 2]
    assert cell.config["overlap"] == "expand+fold" and cell.config["engine"] == "pallas_sparse"
    assert cell.traffic == bench.cell("graph500-s15.exact-stream").traffic
    assert NEW <= {m["name"] for m in cell.per_layer}
    assert not NEW & {m["name"] for m in bench.cell("graph500-s15.exact-stream").per_layer}
    for m in bench.spec["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == "collectives"


def partition_span(**attrs):
    return SimpleNamespace(name="bc.setup.partition", start_ns=0, end_ns=1, attrs=attrs)


@pytest.fixture
def read(monkeypatch, bench):
    from repro.core import spans

    def read(run, ctx=CHIP):
        monkeypatch.setattr(spans, "last_run", lambda: run)
        return bench.metric_reader("collective.level_mib")(ctx)

    return read


def test_level_mib_sums_both_directions(read):
    run = (partition_span(mesh="2x2", exchange_bytes_fwd=24 * 2**20,
                          exchange_bytes_bwd=32 * 2**20 + 65536),)
    assert read(run) == pytest.approx(56.0625)
    assert read((partition_span(exchange_bytes_fwd=0, exchange_bytes_bwd=0),)) == 0.0


@pytest.mark.parametrize("run", [
    None,
    (partition_span(),),  # a program whose span carries no byte counts
    (partition_span(exchange_bytes_fwd=1),),
    (SimpleNamespace(name="bc.setup.layout", start_ns=0, end_ns=1,
                     attrs={"exchange_bytes_fwd": 1, "exchange_bytes_bwd": 1}),),
], ids=["no-run", "no-attrs", "one-attr", "other-span"])
def test_level_mib_has_nothing_to_read(read, run):
    assert read(run) is None


def test_level_mib_reads_nothing_off_a_listed_chip(read):
    run = (partition_span(exchange_bytes_fwd=1, exchange_bytes_bwd=1),)
    assert read(run, ctx={"peaks": None}) is None


def test_a_traced_2x2_run_ships_what_its_spans_say(bench):
    """The cell at SCALE 8 with 16-root rounds on four host devices: the
    CPU has no peaks, so the traced run reports neither collective metric;
    read as on a listed chip, the spans give the bytes of one expand hop
    and one fold hop a level."""
    import jax

    from bench import run as bench_run
    from repro.core import spans

    cell = bench.cell(CELL)
    cfg = json.loads(json.dumps(cell.config))
    cfg["graph"] = dict(cfg["graph"], scale=8, edge_factor=8)
    cell = dataclasses.replace(cell, config=cfg, traffic=dict(
        cell.traffic, batch_size=16, trace_seconds=0.0))
    out = bench_run.run_cell(
        bench, cell, seed=2**31 + 5, seconds=0.0, trace=True,
        devices=jax.devices()[:4], peaks=bench_run.load_peaks(), compile_cache=False,
    )
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"setup.compile_s"}
    (part,) = [s for s in spans.last_run() if s.name == "bc.setup.partition"]
    assert part.attrs["mesh"] == "2x2" and part.attrs["ring_steps"] == 2
    chunk, s = 256 // 4, 16
    block = chunk * s * 4
    fwd, bwd = 2 * block + block, 3 * block + chunk * 4 + block
    assert (part.attrs["exchange_bytes_fwd"], part.attrs["exchange_bytes_bwd"]) == (fwd, bwd)
    value = bench.metric_reader("collective.level_mib")(dict(CHIP))
    assert value == pytest.approx((fwd + bwd) / 2**20)
