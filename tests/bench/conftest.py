"""A small copy of the benchmark for the tests: the real ``bench/`` tree
beside a ``BENCHMARK.json`` whose cells are cut to CPU sizes, added as
files the way a later cell or configuration would be.

Each small cell is one file, ``tests/bench/tiny/<cell>.json``: its
configuration (``config``, with the ``name`` it is written under), its
traffic mix (``traffic``, likewise), the chips it asks for, the supersteps
its check follows (``steps``), its limits (``compared``; set from CPU
readings of the cell, program and fp8 control against the reference, so
that sound runs pass and the control fails) and ``stands_for``: the real
cell whose ``workloads``-restricted per-layer metrics it takes. A later
cell adds its file and edits none."""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def tiny_cells(source: str = REPO) -> dict:
    """Cell name -> the body of its file, for every small cell of the
    checkout ``source``."""
    return {os.path.basename(path)[:-len(".json")]: _load(path)
            for path in sorted(glob.glob(os.path.join(
                source, "tests", "bench", "tiny", "*.json")))}


def _load(path: str):
    with open(path) as f:
        return json.load(f)


def _write(path: str, body) -> None:
    """Write ``body`` as JSON; a file a cell before wrote has to agree."""
    if os.path.exists(path) and _load(path) != body:
        raise ValueError(f"two small cells disagree on {path}")
    with open(path, "w") as f:
        json.dump(body, f)


def make_tiny_bench(root: str, source: str = REPO) -> str:
    """Write the small benchmark of the checkout ``source`` under
    ``root``; returns ``root``."""
    shutil.copytree(os.path.join(source, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    decl = _load(os.path.join(source, "BENCHMARK.json"))
    decl["configs"], decl["workloads"] = [], []
    small: dict = {}
    for cell, body in tiny_cells(source).items():
        config = dict(body["config"])
        traffic = dict(body["traffic"])
        tname = traffic.pop("name")
        path = f"bench/configs/{config['name']}.json"
        _write(os.path.join(root, path), config)
        if config["name"] not in [c["name"] for c in decl["configs"]]:
            decl["configs"].append({"name": config["name"], "source": "test",
                                    "file": path, "reduced": [],
                                    "why": "test"})
        _write(os.path.join(root, "bench", "traffic", f"{tname}.json"),
               traffic)
        decl["workloads"].append({"name": cell, "config": config["name"],
                                  "traffic": tname,
                                  "chips": body.get("chips", 1),
                                  "why": "test"})
        _write(os.path.join(root, "bench", "limits", f"{cell}.json"),
               {"steps": body["steps"], "compared": body["compared"]})
        small.setdefault(body["stands_for"], []).append(cell)
    for m in decl["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [c for real in m["workloads"]
                              for c in small.get(real, [])]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(decl, f)
    return root


@pytest.fixture
def tiny_bench(tmp_path):
    from bench.spec import Bench

    root = make_tiny_bench(str(tmp_path))
    return Bench(root)
