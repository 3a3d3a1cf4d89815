"""Model FLOPs per token from ``bench/flops/`` against hand counts."""
import pytest

from bench.flops import model_flops_per_token
from bench.spec import Bench


@pytest.fixture(scope="module")
def bench():
    return Bench()


def flops(bench, config, seq=4096):
    model = bench.config(config)["model"]
    return model_flops_per_token(model, seq,
                                 lambda k: bench.layer_kind("flops", k))


def test_qwen3_chip_share_matches_the_hand_count(bench):
    # 6 x 714 M parameters (the tied embedding serves as the head) plus
    # the attention products, 12 x 8 layers x 2048 x 4096: 5.09 GFLOP.
    params = 151936 * 2048 + 8 * (2048 * 2048 * 2 + 2048 * 1024 * 2
                                  + 3 * 2048 * 6144)
    assert params == pytest.approx(714e6, rel=1e-3)
    hand = 6 * params + 12 * 8 * 2048 * 4096
    assert flops(bench, "qwen3-1.7b") == pytest.approx(hand, rel=1e-12)
    assert flops(bench, "qwen3-1.7b") == pytest.approx(5.09e9, rel=1e-3)


def test_falcon_mamba_share_counts_projections_conv_and_scan(bench):
    d, di, r, st, k, v = 4096, 8192, 256, 16, 4, 32512
    layer = 6 * (2 * d * di + 2 * di * r + 2 * di * st + di * d + k * di) \
        + 18 * di * st
    assert flops(bench, "falcon-mamba-7b") == pytest.approx(
        6 * d * v + 4 * layer, rel=1e-12)


def test_attention_term_grows_with_the_sequence(bench):
    assert flops(bench, "qwen3-1.7b", 8192) - flops(bench, "qwen3-1.7b") \
        == pytest.approx(12 * 8 * 2048 * 4096)
