"""Find a cell's data files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one op kind or
one per-layer metric is a file of its own under ``chipbench/``; nothing here
knows any of them by name. A later PR adds files and appends entries.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_module(*parts: str):
    """Import ``chipbench/<parts>`` by path (metric names carry dots, so the
    files are not importable by module name)."""
    path = os.path.join(HERE, *parts)
    name = "chipbench._file." + "/".join(parts).replace(".", "_").replace("/", ".")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


class Cell:
    """One entry of ``workloads`` with everything its name leads to."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or benchmark()
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = next(c for c in bench["configs"] if c["name"] == self.entry["config"])
        with open(os.path.join(ROOT, cfg_entry["file"])) as fh:
            self.config = json.load(fh)
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.op_kind = self.traffic["op"]

        def mine(metric):
            return name in metric.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]

    def op_module(self):
        return load_module("ops", self.op_kind + ".py")

    def reference_module(self):
        return load_module("references", self.entry["config"] + ".py")

    def reader(self, kind: str, metric_name: str):
        """``kind`` is ``end_to_end`` or ``layer_metrics``: the metric's own file."""
        return load_module(kind, metric_name + ".py").read
