"""A small copy of the benchmark for the tests: the real ``bench/`` tree
beside a ``BENCHMARK.json`` whose cells are cut to CPU sizes, added as
files the way a later cell or configuration would be."""
from __future__ import annotations

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

TINY_MODELS = {
    "tiny-qwen": {
        "arch": "qwen3-1.7b",
        "model": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 256, "qk_norm": True,
                  "rope_theta": 1000000.0, "tie_embeddings": True,
                  "dtype": "bfloat16",
                  "pattern": [{"mixer": "attn", "ffn": "mlp"}]},
    },
    "tiny-mamba": {
        "arch": "falcon-mamba-7b",
        "model": {"num_layers": 2, "d_model": 64, "vocab_size": 256,
                  "ssm_state": 4, "ssm_conv": 4, "ssm_expand": 2,
                  "dt_rank": 4, "tie_embeddings": False,
                  "dtype": "bfloat16",
                  "pattern": [{"mixer": "mamba", "ffn": "none"}]},
    },
}

TINY_TRAFFIC = {
    "tiny-dfl": {"nodes": 2, "topology": "ring", "tau1": 2, "tau2": 2,
                 "superstep": 2, "batch": 2, "seq": 32, "optimizer": "sgd",
                 "lr": 0.3, "noniid": 0.5, "branching": 16,
                 "compressor": None, "use_kernels": False, "gamma": 0.6,
                 "pool": 3},
    "tiny-topk": {"nodes": 2, "topology": "ring", "tau1": 1, "tau2": 1,
                  "superstep": 1, "batch": 2, "seq": 32, "optimizer": "sgd",
                  "lr": 0.3, "noniid": 0.5, "branching": 16,
                  "compressor": {"name": "top_k", "kwargs": {"frac": 0.25}},
                  "use_kernels": True, "gamma": 0.6, "pool": 3},
}

# set from CPU readings of these cells (program against reference, and the
# fp8 control against it) so that sound runs pass and the control fails
TINY_LIMITS = {
    "tiny-qwen.dfl": {"loss": 0.01, "update": 0.01, "spread": 0.01},
    "tiny-mamba.dfl": {"loss": 0.01, "update": 0.009, "spread": 0.01},
    "tiny-qwen.topk": {"loss": 0.0005, "update": 0.05, "spread": 0.05,
                       "hat": 0.01},
}

TINY_CELLS = [("tiny-qwen.dfl", "tiny-qwen", "tiny-dfl"),
              ("tiny-mamba.dfl", "tiny-mamba", "tiny-dfl"),
              ("tiny-qwen.topk", "tiny-qwen", "tiny-topk")]


def make_tiny_bench(root: str) -> str:
    """Write the small benchmark under ``root``; returns ``root``."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        decl = json.load(f)
    decl["configs"], decl["workloads"] = [], []
    for name, body in TINY_MODELS.items():
        path = f"bench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(dict(body, name=name, reference={"rms_eps": 1e-6}), f)
        decl["configs"].append({"name": name, "source": "test",
                                "file": path, "reduced": [], "why": "test"})
    for name, body in TINY_TRAFFIC.items():
        with open(os.path.join(root, "bench", "traffic", f"{name}.json"),
                  "w") as f:
            json.dump(body, f)
    for cell, config, traffic in TINY_CELLS:
        decl["workloads"].append({"name": cell, "config": config,
                                  "traffic": traffic, "chips": 1,
                                  "why": "test"})
        with open(os.path.join(root, "bench", "limits", f"{cell}.json"),
                  "w") as f:
            json.dump({"steps": 2 if traffic == "tiny-topk" else 1,
                       "compared": TINY_LIMITS[cell]}, f)
    for m in decl["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-qwen.topk"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(decl, f)
    return root


@pytest.fixture
def tiny_bench(tmp_path):
    from bench.spec import Bench

    root = make_tiny_bench(str(tmp_path))
    return Bench(root)
