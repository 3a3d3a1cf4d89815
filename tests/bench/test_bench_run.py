"""One run of a cell: no fallback to the CPU, nothing printed where the
program is missing, and a result object with exactly the contract's keys.
The small cells run on the CPU through ``run_cell(require_chip=False)``,
which skips only the look for a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.run import run_cell
from bench.spec import ROOT

SEED = 2**31 + 11


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.dfl",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_checkout_of_the_benchmark_alone_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _quiet(*args, **kwargs):
    pass


@pytest.mark.parametrize("trace", [False, True])
def test_result_has_exactly_the_contract_keys(tiny_bench, monkeypatch,
                                              trace):
    if trace:
        v5e = tiny_bench.peaks("TPU v5 lite")
        monkeypatch.setattr(type(tiny_bench), "peaks", lambda self, k: v5e)
    r = run_cell(tiny_bench, "tiny-qwen.dfl", SEED, 0.05, trace,
                 require_chip=False, log=_quiet)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown", "checks"] if trace else ["checks"]
    assert list(r) == keys
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    dev = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        dev |= {"busy_s", "window_s"}
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(r["metrics"]) == {"tokens_per_s", "hbm_peak_gb",
                                     "setup_s"}
        assert all(m["value"] > 0 for m in r["metrics"].values())
    assert set(r["device"]) == dev
    assert set(r["checks"]) == {"loss", "update", "spread", "drift",
                                "drift_l1"}
    assert all(set(c) == {"value", "limit"} for c in r["checks"].values())
    line = json.dumps(r)
    assert json.loads(line) == r and "\n" not in line
