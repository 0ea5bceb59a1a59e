"""Finding a cell's files by name.

``BENCHMARK.json`` lists the cells; each cell's parameters live in
``bench/workloads/<cell>.json`` and its model configuration in
``bench/configs/<config>.json``.  Nothing here knows any particular cell,
so a later cell is added with data files alone.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List

__all__ = ["ROOT", "BENCH_DIR", "Cell", "load_benchmark", "load_cell",
           "per_layer_metrics"]

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict          # bench/configs/<config>.json
    workload: Dict        # bench/workloads/<cell>.json

    @property
    def model(self) -> Dict:
        """The configuration's sizes as run, with the cell's sequence."""
        return dict(self.config["model"], seq_len=self.workload["seq_len"])

    @property
    def tokens_per_step(self) -> int:
        return self.workload["global_batch"] * self.workload["seq_len"]


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict:
    return _read(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    work = _read(os.path.join(root, "bench", "workloads", name + ".json"))
    conf = _read(os.path.join(root, "bench", "configs",
                              entry["config"] + ".json"))
    return Cell(name, entry["chips"], conf, work)


def per_layer_metrics(cell: str, root: str = ROOT) -> List[Dict]:
    """The per-layer metrics that this cell reports."""
    return [m for m in load_benchmark(root)["per_layer"]
            if cell in m.get("workloads", [cell])]
