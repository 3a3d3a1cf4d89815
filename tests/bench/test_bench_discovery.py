"""The benchmark finds configurations, cells, traffic, limits, per-layer
metrics and layer kinds by name from files, so that a later cell,
configuration or metric is added as files and entries alone; and the
declared ``BENCHMARK.json`` keeps to the shape the contract gives it."""
import json
import os
import re

import pytest

from bench.spec import ROOT as REPO, Bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def decl():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_declared_piece_resolves_to_its_files(decl):
    bench = Bench(REPO)
    for cell in decl["workloads"]:
        config = bench.config(cell["config"])
        assert config["name"] == cell["config"]
        assert bench.traffic(cell["traffic"])["nodes"] >= 2
        assert "steps" in bench.limits(cell["name"])
        kinds = {k for spec in config["model"]["pattern"]
                 for k in (spec["mixer"], spec["ffn"]) if k != "none"}
        for family in ("flops", "reference"):
            for kind in kinds:
                assert bench.layer_kind(family, kind) is not None
        for m in bench.per_layer(cell["name"]):
            assert callable(bench.metric_reader(m["name"]))
    for c in decl["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert set(body["reduced"]) == set(body["published"])


def test_benchmark_json_keeps_the_contract_shape(decl):
    assert sorted(decl) == ["command", "configs", "end_to_end", "paths",
                            "per_layer", "run_seconds", "workloads"]
    assert decl["command"] == ["python3", "bench/run.py"]
    assert 1 <= decl["run_seconds"] <= 51
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in decl[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.add(entry["name"])
    assert len(names) == sum(len(decl[g]) for g in
                             ("configs", "workloads", "end_to_end",
                              "per_layer"))
    e2e = {m["name"] for m in decl["end_to_end"]}
    assert "setup_s" in e2e
    for m in decl["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in decl["workloads"]}
    for m in decl["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    for w in decl["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_added_files_are_found_by_name(tiny_bench):
    # the small cells, configurations and traffic of the fixture are
    # added files, found without editing any that were there
    cell = tiny_bench.cell("tiny-mamba.dfl")
    assert tiny_bench.config(cell["config"])["arch"] == "falcon-mamba-7b"
    assert tiny_bench.traffic(cell["traffic"])["seq"] == 32
    assert tiny_bench.limits("tiny-mamba.dfl")["compared"]
    # a per-layer metric added as one file and one entry
    path = os.path.join(tiny_bench.dir, "metrics", "tokens.seen.py")
    with open(path, "w") as f:
        f.write("def read(summary):\n    return summary.get('tokens')\n")
    tiny_bench.decl["per_layer"].append(
        {"name": "tokens.seen", "unit": "tokens", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "tokens_per_s", "workloads": ["tiny-qwen.dfl"]})
    names = [m["name"] for m in tiny_bench.per_layer("tiny-qwen.dfl")]
    assert "tokens.seen" in names and "topk_mask_roofline" not in names
    assert tiny_bench.metric_reader("tokens.seen")({"tokens": 7}) == 7
    assert "topk_mask_roofline" in [
        m["name"] for m in tiny_bench.per_layer("tiny-qwen.topk")]


def test_unknown_cell_and_missing_files_raise(tiny_bench):
    with pytest.raises(KeyError):
        tiny_bench.cell("no-such.cell")
    with pytest.raises(FileNotFoundError):
        tiny_bench.metric_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        tiny_bench.layer_kind("flops", "moe")


def test_reference_parts_are_found_by_name(tiny_bench, monkeypatch):
    # the reference's topology, compressor and optimizer are files named
    # by the traffic mix; a mix with a new compressor adds its file alone
    from bench.reference import dfl

    for cell in ("tiny-qwen.dfl", "tiny-qwen.topk"):
        traffic = tiny_bench.traffic(tiny_bench.cell(cell)["traffic"])
        assert dfl.part("topology", traffic["topology"]).matrix(2).sum() == 2
        assert callable(dfl.part("optim", traffic["optimizer"]).update)
    monkeypatch.setattr(dfl, "HERE", os.path.join(tiny_bench.dir,
                                                  "reference"))
    with open(os.path.join(dfl.HERE, "compress", "sign_half.py"), "w") as f:
        f.write("import jax.numpy as jnp\n\n\ndef compress(diff, scale):\n"
                "    return scale * jnp.sign(diff)\n")
    config = tiny_bench.config("tiny-qwen")
    traffic = dict(tiny_bench.traffic("tiny-topk"),
                   compressor={"name": "sign_half",
                               "kwargs": {"scale": 0.5}})
    ref = dfl.Reference(config["model"], traffic)
    import jax.numpy as jnp

    assert ref.compress(jnp.array([-2.0, 3.0])).tolist() == [-0.5, 0.5]
    with pytest.raises(FileNotFoundError):
        dfl.Reference(config["model"], dict(traffic, optimizer="adam"))
    with pytest.raises(FileNotFoundError):
        dfl.Reference(config["model"], dict(traffic, topology="torus"))
