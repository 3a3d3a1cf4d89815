"""What the benchmark reads of the Moonlight cell: the model FLOPs of its
chip share (``bench/flops/mla.py``, ``bench/flops/moe.py``), the named
scopes of latent attention and the expert layer in the compiled
superstep, and the expert layer's per-layer readers."""
import pytest

from bench import scopes
from bench.flops import model_flops_per_token
from bench.program import Program
from bench.spec import Bench
from bench.traffic import generator
from test_bench_scopes import fresh_compile  # noqa: F401  (a fixture)


def reader(name):
    return Bench().metric_reader(name)


def test_moonlight_share_counts_mla_the_router_and_held_experts():
    # per token: MLA's projections (13.76 M weights) and its score and
    # value products over 192 + 128 head dims; the router over all 64
    # experts, 2 shared experts, and 6 x 16 / 64 = 1.5 held experts a
    # token of the SwiGLU of 1408; one dense layer of 11264; the head over
    # the quarter vocabulary
    bench = Bench()
    model = bench.config("moonlight-16b-a3b")["model"]
    n = model["num_layers"] - 1
    mla_w = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert mla_w == pytest.approx(13.76e6, rel=1e-3)
    mla = 6 * mla_w + 6 * 16 * 4096 * (192 + 128)
    swiglu = 3 * 2048 * 1408
    moe = 6 * (2048 * 64 + 2 * swiglu + 1.5 * swiglu)
    hand = 6 * 2048 * 40960 + (n + 1) * mla + 6 * 3 * 2048 * 11264 + n * moe
    got = model_flops_per_token(model, 4096,
                                lambda k: bench.layer_kind("flops", k))
    assert got == pytest.approx(hand, rel=1e-12)
    assert mla + moe == pytest.approx(391e6, rel=2e-3)
    assert mla + 6 * 3 * 2048 * 11264 == pytest.approx(624e6, rel=2e-3)


def test_the_compiled_expert_layer_names_its_parts(tiny_bench, fresh_compile):
    # the small Moonlight cell's superstep (four nodes stacked on one CPU
    # device): latent attention and the expert layer carry their scopes,
    # forward and backward, and the routing, grouped matmuls, combine and
    # shared experts each theirs
    c = tiny_bench.cell("tiny-moonlight.ring4")
    config = tiny_bench.config(c["config"])
    traffic = tiny_bench.traffic(c["traffic"])
    program = Program(config, traffic)
    pool = generator.make_pool(traffic, config["model"]["vocab_size"], 3)
    program.warm(program.build_state(3), program.batches(pool[0]))
    paths = scopes.scope_paths(program.compiled.as_text())
    phases = {scopes.phase(m) for m in paths.values()}
    for part in ("model.mla", "moe.route", "moe.experts", "moe.combine",
                 "moe.shared"):
        assert part in phases and f"{part}/backward" in phases, part
    under_moe = {m[m.index("model.moe") + 1] for m in paths.values()
                 if "model.moe" in m[:-1]}
    assert {"moe.route", "moe.experts", "moe.combine",
            "moe.shared"} <= under_moe


def test_the_expert_layer_readers():
    # device seconds under model.mla and model.moe a round, and the
    # grouped matmuls' share of the peak from the counted rows; the TPU's
    # grouped-matmul kernel calls keep no op_name, so their time is
    # unscoped, and the expert layer's readers add it
    s = {"scopes": {"model.mla": 3.0, "model.moe": 4.0, "moe.experts": 1.0},
         "scope_ops": {"unscoped ragged-dot-none": 0.75,
                       "unscoped ragged-dot-metadata": 0.25,
                       "unscoped fusion:kOutput": 9.0,
                       "moe.experts fusion:kLoop": 1.0},
         "rounds": 2, "chips": 4, "round_metrics": {"moe_rows": 1e6},
         "model": {"d_model": 2048, "expert_d_ff": 1408},
         "peaks": {"bf16_flops": 1.97e14}}
    assert reader("mla_s_per_round")(s) == pytest.approx(1.5)
    assert reader("moe_s_per_round")(s) == pytest.approx(2.5)
    flops = 1e6 * 24 * 2048 * 1408 / 4
    assert reader("moe_gmm_roofline")(s) == pytest.approx(
        100 * flops / 1.97e14 / 2.0)
    no_remat = dict(s, model=dict(s["model"], remat=False))
    assert reader("moe_gmm_roofline")(no_remat) == pytest.approx(
        100 * flops * 18 / 24 / 1.97e14 / 2.0)
    for name in ("mla_s_per_round", "moe_s_per_round", "moe_gmm_roofline"):
        assert reader(name)(dict(s, scopes={"dfl.local": 1.0})) is None
    assert reader("moe_gmm_roofline")(dict(s, round_metrics={})) is None
