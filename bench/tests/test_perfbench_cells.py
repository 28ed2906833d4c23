"""BENCHMARK.json resolves by name, and grows by files and entries alone."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from bench import cells

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark(ROOT)


def test_every_cell_resolves_to_its_files(bench):
    spec = bench.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    for entry in spec["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert entry["file"].startswith("bench/configs/")
    for name in bench.cell_names():
        cell = bench.cell(name)
        assert cell.chips == cell.config["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"mteps", "setup_s"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(bench.metric_reader(m["name"]))


def test_names_units_and_moves_follow_the_rules(bench):
    spec = bench.spec
    e2e = {m["name"] for m in spec["end_to_end"]}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in spec[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("higher", "lower")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in bench.cell_names()
    assert len(json.dumps(spec)) < 64 * 1024


def test_new_configuration_traffic_and_metric_are_picked_up(tmp_path, bench):
    (tmp_path / "bench").mkdir()
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tmp_path / "bench" / sub)
    spec = json.loads(json.dumps(bench.spec))
    (tmp_path / "bench/configs/ring-1chip.json").write_text(json.dumps({
        "name": "ring-1chip", "source": "https://example.org/ring", "chips": 1,
        "graph": {"generator": "lattice", "rows": 1, "cols": 256}, "mesh": [1, 1],
        "engine": "pallas_sparse", "overlap": "none", "reduced": {}, "reference_batch": 128,
        "check": {"span_err": 1e-6, "span_med": 1e-7, "final_err": 1e-9},
    }))
    (tmp_path / "bench/traffic/burst.json").write_text(json.dumps(
        dict(json.loads((ROOT / "bench/traffic/exact-stream.json").read_text()),
             name="burst", batch_size=256)))
    (tmp_path / "bench/metrics/device.busy_s.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    spec["configs"].append({"name": "ring-1chip", "source": "https://example.org/ring",
                            "file": "bench/configs/ring-1chip.json", "reduced": [],
                            "why": "a new configuration"})
    spec["workloads"].append({"name": "ring.burst", "config": "ring-1chip",
                              "traffic": "burst", "chips": 1, "why": "a new cell"})
    spec["per_layer"].append({"name": "device.busy_s", "unit": "s", "better": "higher",
                              "source": "device_trace", "layer": "device", "moves": "mteps",
                              "workloads": ["ring.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    grown = cells.load_benchmark(tmp_path)
    cell = grown.cell("ring.burst")
    assert cell.config["graph"]["cols"] == 256 and cell.traffic["batch_size"] == 256
    assert [m["name"] for m in cell.per_layer][-1] == "device.busy_s"
    assert grown.metric_reader("device.busy_s")({}) == 1.5
    # the new metric names only the new cell
    old = grown.cell(bench.cell_names()[0])
    assert "device.busy_s" not in [m["name"] for m in old.per_layer]


def run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("XLA_FLAGS", None)
    first = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", first, "--seed", "2147483659",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_run_refuses_a_cpu_only_jax():
    proc = run_bench(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_fails_in_a_checkout_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace*"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = run_bench(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
