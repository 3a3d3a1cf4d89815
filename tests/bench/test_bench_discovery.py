"""The benchmark finds configurations, cells, traffic, limits, per-layer
metrics and layer kinds by name from files, so that a later cell,
configuration or metric is added as files and entries alone; and the
declared ``BENCHMARK.json`` keeps to the shape the contract gives it."""
import hashlib
import json
import os
import re
import shutil

import pytest

from bench.flops import model_flops_per_token
from bench.spec import ROOT as REPO, Bench
from conftest import make_tiny_bench, tiny_cells

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
        tiny_bench.layer_kind("flops", "no_such_kind")


def test_a_multichip_cell_holds_one_node_a_chip(decl):
    # a cell asks for four chips only for what exists across chips: here
    # the gossip between nodes that each hold a chip of their own; a small
    # CPU cell on as many devices stands for it
    bench = Bench(REPO)
    small = tiny_cells()
    for w in decl["workloads"]:
        if w["chips"] > 1:
            assert bench.traffic(w["traffic"])["nodes"] == w["chips"]
            assert any(body["stands_for"] == w["name"]
                       for body in small.values())
    for body in small.values():
        if body.get("chips", 1) > 1:
            assert body["chips"] == body["traffic"]["nodes"]
    ring4 = bench.traffic("ring4")
    assert ring4["nodes"] == 4 and ring4["topology"] == "ring"
    assert ring4["compressor"] is None
    assert small["tiny-qwen.ring4"]["chips"] == 4


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_layer_kind_and_small_cell_are_added_as_files_alone(tmp_path):
    # a later configuration brings a layer kind (its model FLOPs and its
    # plain reference) and a small CPU cell standing for its real cell, as
    # new files in a copy of the checkout; the benchmark finds them and no
    # file that was there changes
    src = tmp_path / "checkout"
    os.makedirs(src)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), src)
    for d in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(REPO, d), src / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(src)
    (src / "bench" / "flops" / "moe_test.py").write_text(
        "def flops_per_token(model, seq):\n"
        "    return 6.0 * 3 * model['d_model'] * model['d_ff']\n")
    (src / "bench" / "reference" / "moe_test.py").write_text(
        "def params(model):\n"
        "    return {'ln2': ((model['d_model'],), 'param', ('zeros', 0.0))}"
        "\n\n\ndef apply(p, h, pos, model, ops):\n    return h\n")
    body = tiny_cells()["tiny-qwen.dfl"]
    body["config"] = dict(body["config"], name="tiny-moe")
    body["config"]["model"] = dict(
        body["config"]["model"], pattern=[{"mixer": "attn",
                                           "ffn": "moe_test"}])
    body["stands_for"] = "qwen3-1.7b.cdfl-topk"
    with open(src / "tests" / "bench" / "tiny" / "tiny-moe.dfl.json",
              "w") as f:
        json.dump(body, f)
    after = _digests(src)
    assert {k: after[k] for k in before} == before
    bench = Bench(make_tiny_bench(str(tmp_path / "small"), source=str(src)))
    cell = bench.cell("tiny-moe.dfl")
    model = bench.config(cell["config"])["model"]
    assert list(bench.layer_kind("reference", "moe_test").params(model)) \
        == ["ln2"]
    total = model_flops_per_token(model, 32,
                                  lambda k: bench.layer_kind("flops", k))
    assert total == pytest.approx(
        6 * 64 * 256 + 2 * 6.0 * 3 * 64 * 128
        + 2 * bench.layer_kind("flops", "attn").flops_per_token(model, 32))
    # the new cell takes the metrics of the real cell it stands for
    assert "topk_mask_roofline" in [m["name"]
                                    for m in bench.per_layer("tiny-moe.dfl")]
    assert set(tiny_cells(str(src))) == set(tiny_cells()) | {"tiny-moe.dfl"}


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
