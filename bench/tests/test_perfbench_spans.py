"""The readers of the program's spans (setup.layout_s, setup.transfer_s,
driver.turnaround_ms) on hand-built runs."""
import pathlib
import sys
from types import SimpleNamespace

import pytest

from bench import cells

ROOT = pathlib.Path(__file__).resolve().parents[2]
MS = 1_000_000
#: what a run on a listed chip gives the readers
CHIP = {"peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def sp(name, start_ms, end_ms, **attrs):
    return SimpleNamespace(name=name, start_ns=int(start_ms * MS), end_ns=int(end_ms * MS),
                           attrs=attrs)


def hand_run(blocks=6, drop=()):
    """An entry run: set-up spans, then blocks of 500 ms whose turnaround
    (drain of k to dispatch of k+1) is 4 ms plus k/10 ms."""
    run = [sp("bc.entry", 0, 99_999), sp("bc.setup.schedule", 1, 21),
           sp("bc.setup.partition", 21, 321), sp("bc.setup.layout", 321, 5_321),
           sp("bc.setup.transfer", 5_321, 7_321, bytes=4_294_967_296)]
    t = 8_000.0
    for k in range(1, blocks + 1):
        run.append(sp("bc.driver.block", t, t + 500, block=k))
        run.append(sp("bc.driver.dispatch", t, t + 1, block=k))
        run.append(sp("bc.driver.drain", t + 1, t + 490, block=k))
        t += 490 + 4 + k / 10 - 1
    return tuple(s for s in run if s.name not in drop)


@pytest.fixture
def read(monkeypatch):
    from repro.core import spans

    bench = cells.load_benchmark(ROOT)

    def read(metric, run, ctx=CHIP):
        monkeypatch.setattr(spans, "last_run", lambda: run)
        return bench.metric_reader(metric)(ctx)

    return read


def test_expected_values(read):
    run = hand_run()
    assert read("setup.layout_s", run) == pytest.approx(20e-3 + 0.3 + 5.0)
    assert read("setup.transfer_s", run) == pytest.approx(2.0)
    # pairs (3, 4), (4, 5), (5, 6): 4.3, 4.4, 4.5 ms
    assert read("driver.turnaround_ms", run) == pytest.approx(4.4)


@pytest.mark.parametrize("metric", ["setup.layout_s", "setup.transfer_s",
                                    "driver.turnaround_ms"])
def test_nothing_to_read_without_a_run(read, metric):
    assert read(metric, None) is None


@pytest.mark.parametrize("metric, drop", [
    ("setup.layout_s", ("bc.setup.layout",)),
    ("setup.layout_s", ("bc.setup.schedule",)),
    ("setup.transfer_s", ("bc.setup.transfer",)),
    ("driver.turnaround_ms", ("bc.driver.drain",)),
    ("driver.turnaround_ms", ("bc.driver.dispatch",)),
])
def test_nothing_to_read_with_a_span_missing(read, metric, drop):
    assert read(metric, hand_run(drop=drop)) is None


def test_turnaround_needs_a_block_after_the_third(read):
    assert read("driver.turnaround_ms", hand_run(blocks=3)) is None
    assert read("driver.turnaround_ms", hand_run(blocks=4)) == pytest.approx(4.3)


@pytest.mark.parametrize("metric", ["setup.layout_s", "setup.transfer_s",
                                    "driver.turnaround_ms"])
def test_nothing_to_read_off_a_listed_chip_or_without_the_recorder(read, monkeypatch, metric):
    assert read(metric, hand_run(), ctx={"peaks": None}) is None
    import repro.core

    # a program without the recorder
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    monkeypatch.delattr(repro.core, "spans")
    assert read(metric, hand_run()) is None
