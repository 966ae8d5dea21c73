"""BENCHMARK.json and the files its entries name.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file found by the entry's name:
  configs/<config>.json, workloads/<cell>.json, metrics/<metric>.json.
A later PR adds entries and files; nothing here lists names.
"""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def _load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


class Manifest:
    def __init__(self, root: str = ROOT) -> None:
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError("no workload %r in BENCHMARK.json (have: %s)"
                           % (name, ", ".join(sorted(self.cells))))
        return self.cells[name]

    def config_params(self, cell_name: str) -> dict:
        cfg = self.configs[self.cell(cell_name)["config"]]
        return _load(os.path.join(self.root, cfg["file"]))

    def workload_params(self, cell_name: str) -> dict:
        return _load(os.path.join(
            self.root, "benchmark", "workloads", cell_name + ".json"))

    def metric_params(self, metric_name: str) -> dict:
        return _load(os.path.join(
            self.root, "benchmark", "metrics", metric_name + ".json"))

    def _reported_in(self, metric: dict, cell_name: str) -> bool:
        cells = metric.get("workloads")
        return cells is None or cell_name in cells

    def end_to_end(self, cell_name: str) -> list:
        return [m for m in self.doc["end_to_end"]
                if self._reported_in(m, cell_name)]

    def per_layer(self, cell_name: str) -> list:
        return [m for m in self.doc["per_layer"]
                if self._reported_in(m, cell_name)]

    def expected_metrics(self, cell_name: str, trace: bool) -> dict:
        """{name: unit} that a run of this kind has to report.

        An untraced run reports the cell's end-to-end metrics; a traced
        run reports all of the cell's metrics, end-to-end and per-layer,
        so either reading of "each metric of this workload" holds."""
        want = {m["name"]: m["unit"] for m in self.end_to_end(cell_name)}
        if trace:
            want.update({m["name"]: m["unit"]
                         for m in self.per_layer(cell_name)})
        return want
