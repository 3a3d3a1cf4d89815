"""Peaks are keyed by ``device_kind``; a kind the table lacks is an
error, never a default."""
import json
import os

import pytest

from bench.spec import BENCH_DIR, Bench


def test_v5e_peaks_carry_their_source():
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]
    p = Bench().peaks("TPU v5 lite")
    assert p["bf16_flops"] == 1.97e14
    assert p["hbm_bytes_per_s"] == 8.19e11


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no peaks"):
        Bench().peaks(kind)
