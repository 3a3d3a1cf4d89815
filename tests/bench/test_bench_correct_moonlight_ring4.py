"""``correct`` on the small tiny-moonlight.ring4 cell (latent attention and
a held-expert layer), one node a device on four CPU devices (the sparse
engine): a sound run is correct; the fp8 control and each planted fault
are not (see bench_faults_common).

The child process and its cases are the ring's
(``test_bench_correct_ring4``), run on this cell."""
import json
import os
import subprocess
import sys

import pytest

from bench_faults_common import FAULTS
from test_bench_correct_ring4 import HERE, SCRIPT

CELL = "tiny-moonlight.ring4"


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT, HERE,
                        os.path.dirname(os.path.dirname(HERE)), CELL],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_moonlight_sound_run_is_correct(cases):
    assert cases["sound"]["correct"] is True, cases["sound"]["checks"]
    assert cases["sound"]["count"] == 4


def test_moonlight_control_is_not_correct(cases):
    assert cases["control"]["correct"] is False, cases["control"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_moonlight_planted_fault_is_not_correct(cases, fault):
    assert cases[fault]["correct"] is False, cases[fault]["checks"]
