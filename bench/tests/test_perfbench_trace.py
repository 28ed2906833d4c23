"""The trace reduction, on a trace recorded on a TPU v5e and on a
hand-made one with a collective."""
import json
import pathlib

import pytest

from bench import trace as tr

DATA = pathlib.Path(__file__).parent / "data" / "lattice_trace.json"


@pytest.fixture(scope="module")
def record():
    return json.loads(DATA.read_text())


def sweep_busy(ops, lo, hi):
    """Busy nanoseconds by a plain sweep over interval end points."""
    points = []
    for o in ops:
        s, e = max(o[1], lo), min(o[1] + o[2], hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for t, step in sorted(points):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_busy_and_idle_share(record):
    lo, hi = record["window"]
    ops = record["devices"]["TPU:0"]
    summ = tr.summarize(record)
    chip = summ["chips"]["TPU:0"]
    assert summ["window_ns"] == 450_000_000
    assert chip["busy_ns"] == sweep_busy(ops, lo, hi)
    # one block turnaround (about 4.7 ms) lies in this slice of the window
    idle = 1 - chip["busy_ns"] / summ["window_ns"]
    assert 0.009 < idle < 0.012


def test_recorded_kernel_attribution(record):
    lo, hi = record["window"]
    ops = record["devices"]["TPU:0"]
    fwd = [o for o in ops if o[0].startswith("frontier_spmm_sparse")]
    bwd = [o for o in ops if o[0].startswith("dependency_spmm_sparse")]
    chip = tr.summarize(record)["chips"]["TPU:0"]
    assert chip["forward_calls"] == len(fwd) == 119
    assert chip["backward_calls"] == len(bwd) == 137
    assert chip["forward_ns"] == sweep_busy(fwd, lo, hi)
    assert chip["backward_ns"] == sweep_busy(bwd, lo, hi)
    assert chip["collective_ns"] == 0 == chip["collective_exposed_ns"]
    assert {o[3] for o in fwd + bwd} == {"custom-call"}


def test_recorded_breakdown(record):
    top = tr.top_ops(record)
    assert [name for name, _ in top[:2]] == ["dependency_spmm_sparse", "pad"]
    assert all(name != "while" for name, _ in top)  # loops count through their contents
    gaps = tr.idle_gaps(record)
    name, seconds = gaps[0]
    assert name == "driver+stop_rule"  # the block turnaround around the stop rule
    assert 0.004 < seconds < 0.005
    assert len(gaps) <= 10 and len(top) <= 10


@pytest.mark.parametrize("text, want", [
    ("%while.3 = (f32[8]{0}, s32[]) while((f32[8]{0}, s32[]) %tuple.42), condition=%c",
     ["while.3", 5, 7, "while"]),
    ("%dependency_spmm_sparse.6 = f32[8,256]{1,0} custom-call(s32[4]{0} %a)",
     ["dependency_spmm_sparse.6", 5, 7, "custom-call"]),
    ("%all-gather-done.1 = f32[8]{0} all-gather-done((f32[4], f32[8]) %s)",
     ["all-gather-done.1", 5, 7, "all-gather-done"]),
    ("%copy-start.1 = (s32[1,128]{1,0}, u32[]{:S(2)}) copy-start(s32[1,128] %x)",
     ["copy-start.1", 5, 7, "copy-start"]),
])
def test_op_record_parses_hlo_names(text, want):
    assert tr.op_record(text, 5.0, 7.0) == want


def test_collective_exposure_hand_made():
    # window 0..100; chip 0 computes 0..40 and waits in a collective
    # 30..60 (10 of it under compute); chip 1 only waits 50..70
    rec = {
        "window": [0, 100],
        "devices": {
            "TPU:0": [["fusion.1", 0, 40, "fusion"],
                      ["collective-permute-done.2", 30, 30, "collective-permute-done"],
                      ["while.1", 0, 60, "while"]],
            "TPU:1": [["all-gather-done.1", 50, 20, "all-gather-done"],
                      ["frontier_spmm_sparse.1", 80, 30, "custom-call"]],
        },
        "host": [["bench.traced", 0, 100], ["bench.stop_rule", 70, 5]],
    }
    summ = tr.summarize(rec)
    c0, c1 = summ["chips"]["TPU:0"], summ["chips"]["TPU:1"]
    assert c0["busy_ns"] == 60 and c1["busy_ns"] == 40
    assert c0["collective_ns"] == 30 and c0["collective_exposed_ns"] == 20
    assert c1["collective_ns"] == 20 and c1["collective_exposed_ns"] == 20
    assert c1["forward_calls"] == 1 and c1["forward_ns"] == 20  # clipped at 100
    # both chips idle only in 70..80, under the stop rule for half of it
    assert tr.idle_gaps(rec) == [["stop_rule", 10 / 1e9]]
