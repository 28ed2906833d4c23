"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's entry names its file; a traffic mix is
``bench/traffic/<traffic>.json``; a per-layer metric is read by
``bench/metrics/<name>.py``.  Adding any of them is adding files and
entries: nothing here lists them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

__all__ = ["Benchmark", "Cell", "load_benchmark"]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # BENCHMARK.json metric entries this cell reports
    per_layer: list


@dataclasses.dataclass(frozen=True)
class Benchmark:
    root: pathlib.Path
    spec: dict

    @property
    def bench_dir(self) -> pathlib.Path:
        return self.root / "bench"

    def cell_names(self) -> list[str]:
        return [w["name"] for w in self.spec["workloads"]]

    def cell(self, name: str) -> Cell:
        by_name = {w["name"]: w for w in self.spec["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r}; BENCHMARK.json has {sorted(by_name)}")
        w = by_name[name]
        configs = {c["name"]: c for c in self.spec["configs"]}
        entry = configs[w["config"]]
        config = json.loads((self.root / entry["file"]).read_text())
        traffic = json.loads(
            (self.bench_dir / "traffic" / f"{w['traffic']}.json").read_text())

        def applies(metric):
            return name in metric.get("workloads", [name])

        return Cell(
            name=name,
            chips=int(w["chips"]),
            config=config,
            traffic=traffic,
            end_to_end=[m for m in self.spec["end_to_end"] if applies(m)],
            per_layer=[m for m in self.spec["per_layer"] if applies(m)],
        )

    def metric_reader(self, name: str):
        """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
        path = self.bench_dir / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def load_benchmark(root) -> Benchmark:
    root = pathlib.Path(root)
    return Benchmark(root=root, spec=json.loads((root / "BENCHMARK.json").read_text()))
