"""``correct`` on the small tiny-qwen.topk cell: a sound run is correct; the
fp8 control and each planted fault are not (see bench_faults_common)."""
import pytest

from bench_faults_common import (FAULTS, control_is_not_correct,
                                 planted_fault_is_not_correct,
                                 sound_run_is_correct)

CELL = "tiny-qwen.topk"


def test_sound_run_is_correct(tiny_bench):
    sound_run_is_correct(tiny_bench, CELL)


def test_control_is_not_correct(tiny_bench):
    control_is_not_correct(tiny_bench, CELL)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(tiny_bench, monkeypatch, fault):
    planted_fault_is_not_correct(tiny_bench, monkeypatch, CELL, fault)
