"""One run of a cell: no fallback to the CPU, nothing printed where the
program is missing, a result object with exactly the contract's keys, the
program's counters summed over the window, and the slowest superstep
reported. The small cells run on the CPU through
``run_cell(require_chip=False)``, which skips only the look for a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench.program import Program, round_metrics, run_window
from bench.run import run_cell
from bench.spec import ROOT
from bench.traffic import generator

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


def test_round_metrics_sum_every_counter_but_the_loss_over_rounds():
    kept = [{"loss": np.array([5.0, 4.0], np.float32),
             "consensus_sq": np.array([0.5, 0.25], np.float32),
             "tau1": np.array([2, 2], np.int32)},
            {"loss": np.array([3.0, 2.0], np.float32),
             "consensus_sq": np.array([0.125, 0.0625], np.float32),
             "tau1": np.array([2, 1], np.int32)}]
    assert round_metrics(kept) == {"consensus_sq": 0.9375, "tau1": 7.0}
    assert round_metrics([]) == {}


def test_a_window_keeps_the_programs_counters_only_when_asked(tiny_bench):
    c = tiny_bench.cell("tiny-qwen.topk")
    config = tiny_bench.config(c["config"])
    traffic = tiny_bench.traffic(c["traffic"])
    program = Program(config, traffic)
    pool = generator.make_pool(traffic, config["model"]["vocab_size"], SEED)
    state = program.build_state(SEED)
    program.warm(state, program.batches(pool[0]))
    win = run_window(program, state, pool, 0.05)
    assert win.done >= 2
    assert len(win.metrics) == len(win.step_s) == win.done
    assert sum(win.step_s) == pytest.approx(win.wall)
    sums = round_metrics(win.metrics)
    # C-DFL nodes never agree exactly, so the counter is no zero
    per_round = np.concatenate([np.asarray(m["consensus_sq"])
                                for m in win.metrics])
    assert len(per_round) == win.done * traffic["superstep"]
    assert sums["consensus_sq"] == pytest.approx(float(per_round.sum()))
    assert sums["consensus_sq"] > 0 and "loss" not in sums
    # they are summed and printed only when a traced run asks for them
    lines = []
    run_cell(tiny_bench, "tiny-qwen.topk", SEED, 0.05, False,
             require_chip=False,
             log=lambda *a, **k: lines.append(" ".join(map(str, a))))
    assert not any(line.startswith("program counters") for line in lines)


def test_the_slowest_superstep_is_reported_beside_the_median(tiny_bench):
    lines = []
    run_cell(tiny_bench, "tiny-qwen.dfl", SEED, 0.05, False,
             require_chip=False,
             log=lambda *a, **k: lines.append(" ".join(map(str, a))))
    got = [re.match(r"superstep wall \(s\): median ([\d.]+), slowest "
                    r"([\d.]+) \(superstep (\d+) of (\d+)\)", line)
           for line in lines]
    got = [m for m in got if m]
    assert len(got) == 1
    median, slowest, at, done = got[0].groups()
    assert float(slowest) >= float(median) > 0
    assert 0 <= int(at) < int(done)
