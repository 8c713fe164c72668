"""Finds what a cell is made of, by name.

`BENCHMARK.json` names a cell's configuration, traffic mix and chips and
nothing more.  Each belongs to a file of its own under `benchmark/`, found
here by that name, so a later PR adds a cell, a configuration, a traffic mix,
a job kind, a reference, an operation count or a per-layer metric by adding
files and entries — never by editing this module or `run.py`.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _module(kind: str, name: str, bench_dir: str) -> ModuleType:
    """`<bench_dir>/<kind>/<name>.py`, loaded by path (a name may hold dots)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader_beside(path: str, name: str) -> ModuleType:
    """The per-layer reader `name` beside the reader file `path`: for a metric
    that reads what another does and moves another end-to-end metric."""
    return _module("layer_metrics", name, os.path.dirname(os.path.dirname(os.path.abspath(path))))


class Benchmark:
    """`BENCHMARK.json` of one checkout and the files its names point to."""

    def __init__(self, root: str = ROOT) -> None:
        self.root = root
        self.doc = _json(os.path.join(root, "BENCHMARK.json"))
        paths = self.doc["paths"]
        self.bench_dir = os.path.join(root, paths[0])

    # -- cells ---------------------------------------------------------------

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in self.doc['workloads']]}"
        )

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return dict(_json(os.path.join(self.root, c["file"])), name=name)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return dict(_json(os.path.join(self.bench_dir, "traffic", name + ".json")), name=name)

    def peaks(self, device_kind: str) -> Dict[str, float]:
        table = _json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table or device_kind.startswith("_"):
            raise RuntimeError(
                f"no peaks recorded for device kind {device_kind!r}: add it to "
                "benchmark/peaks.json with its source — nothing is measured "
                "against a guessed peak"
            )
        return table[device_kind]

    # -- metrics -------------------------------------------------------------

    def _reported(self, metrics: List[Dict[str, Any]], cell: str) -> List[Dict[str, Any]]:
        return [m for m in metrics if cell in m.get("workloads", [cell])]

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return self._reported(self.doc["end_to_end"], cell)

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        """The per-layer metrics this cell reports: those that list it, and
        those without a list whose `moves` the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self._reported(self.doc["per_layer"], cell) if m["moves"] in e2e]

    # -- code found by name ----------------------------------------------------

    def job(self, name: str) -> ModuleType:
        return _module("jobs", name, self.bench_dir)

    def reader(self, metric: str) -> ModuleType:
        return _module("layer_metrics", metric, self.bench_dir)

    def reference(self, architecture: str) -> ModuleType:
        return _module("reference", architecture, self.bench_dir)

    def flops(self, name: str) -> ModuleType:
        return _module("flops", name, self.bench_dir)

    def program(self, architecture: str) -> ModuleType:
        return _module("programs", architecture, self.bench_dir)

