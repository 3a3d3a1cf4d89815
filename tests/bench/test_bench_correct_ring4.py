"""``correct`` on the small tiny-qwen.ring4 cell, one node a device on four
CPU devices (the sparse engine): a sound run is correct; the fp8 control
and each planted fault are not (see bench_faults_common); and a cell on
several chips that would not resolve to the sparse engine is refused.

The four devices have to exist before JAX starts, so one child process
runs every case and prints one JSON line; each test reads its case."""
import json
import os
import subprocess
import sys

import pytest

from bench_faults_common import FAULTS

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tiny-qwen.ring4"

SCRIPT = r"""
import json, os, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
here, repo, cell = sys.argv[1:4]
sys.path[:0] = [here, repo, os.path.join(repo, "src")]
import jax
import pytest
from conftest import make_tiny_bench
from bench_faults_common import FAULTS, _run, control_is_not_correct
from bench.program import Program
from bench.spec import Bench

out = {}
bench = Bench(make_tiny_bench(tempfile.mkdtemp()))
r = _run(bench, cell)
out["sound"] = {"correct": r["correct"], "checks": r["checks"],
                "count": r["device"]["count"]}
try:
    control_is_not_correct(bench, cell)
    out["control"] = {"correct": False}
except AssertionError as e:
    out["control"] = {"correct": True, "why": str(e)[-2000:]}
for fault in sorted(FAULTS):
    with pytest.MonkeyPatch.context() as mp:
        FAULTS[fault](mp)
        r = _run(bench, cell)
    out[fault] = {"correct": r["correct"], "checks": r["checks"]}
two = bench.cell("tiny-qwen.dfl")
try:
    Program(bench.config(two["config"]), bench.traffic(two["traffic"]),
            jax.devices()[:4])
    out["guard"] = None
except ValueError as e:
    out["guard"] = str(e)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT, HERE,
                        os.path.dirname(os.path.dirname(HERE)), CELL],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(cases):
    assert cases["sound"]["correct"] is True, cases["sound"]["checks"]
    assert cases["sound"]["count"] == 4


def test_control_is_not_correct(cases):
    assert cases["control"]["correct"] is False, cases["control"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(cases, fault):
    assert cases[fault]["correct"] is False, cases[fault]["checks"]


def test_a_multichip_cell_off_the_sparse_engine_is_refused(cases):
    # two nodes over four devices: the node mesh does not enumerate the
    # nodes, the engine resolves to dense, and the Program refuses to stack
    # nodes onto a chip of a cell that asked for several
    assert cases["guard"] and "sparse engine" in cases["guard"]
