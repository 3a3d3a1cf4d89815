"""What the benchmark runs, found by name from the files beside this one.

``BENCHMARK.json`` at the checkout's root lists the cells and metrics. A
cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); its correctness limits are in
``bench/limits/<cell>.json``. A per-layer metric is the reader
``bench/metrics/<metric>.py``; the model FLOPs of a layer kind are in
``bench/flops/<kind>.py``; the plain reference of a layer kind is
``bench/reference/<kind>.py``, and that of the topology, compressor
and optimizer a traffic mix names is ``bench/reference/<family>/<name>.py``
(families ``topology``, ``compress``, ``optim``). A later cell,
configuration or metric adds its own files and entries and edits none of
these.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one Python file by its path (metric and layer-kind files
    carry dots and dashes in their names)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """The benchmark as ``BENCHMARK.json`` declares it, rooted at ``root``
    (a checkout, or a test's scratch copy)."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.dir = os.path.join(root, "bench")
        self.decl = _load_json(os.path.join(root, "BENCHMARK.json"))

    # -- cells ------------------------------------------------------------

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.decl["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{[w['name'] for w in self.decl['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.decl["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return _load_json(os.path.join(self.dir, "traffic", f"{name}.json"))

    def limits(self, cell: str) -> Dict[str, float]:
        return _load_json(os.path.join(self.dir, "limits", f"{cell}.json"))

    # -- metrics ----------------------------------------------------------

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.decl["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.decl["per_layer"]
                if cell in m.get("workloads", [cell])]

    def metric_reader(self, name: str):
        """``read(summary) -> float | None`` of one per-layer metric."""
        mod = load_module(os.path.join(self.dir, "metrics", f"{name}.py"),
                          f"bench_metric_{name.replace('.', '_')}")
        return mod.read

    def layer_kind(self, family: str, kind: str):
        """The module of one layer kind (a mixer or ffn name of the
        config's pattern) under ``bench/<family>/``."""
        return load_module(os.path.join(self.dir, family, f"{kind}.py"),
                           f"bench_{family}_{kind}")

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        table = _load_json(os.path.join(self.dir, "peaks.json"))
        if device_kind not in table["devices"]:
            raise KeyError(
                f"no peaks for device kind {device_kind!r} in "
                f"bench/peaks.json (known: {sorted(table['devices'])})")
        return table["devices"][device_kind]
