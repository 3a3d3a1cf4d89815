"""Step builders produce lowerable artifacts for reduced configs on a tiny
host mesh (no 512-device flag needed: 1x1 mesh, everything replicated)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(1, 1)


def test_reduced_round_lowers_and_runs(mesh):
    arch = REGISTRY["qwen3-1.7b"]
    built = S.build_train_round(arch, "train_4k", mesh, tau1=2, tau2=2,
                                reduced=True)
    # abstract shapes exist
    assert built.meta["nodes"] >= 1
    lowered = built.lower()
    assert lowered is not None


def test_reduced_superstep_lowers(mesh):
    """The fused K-round superstep is a lowerable production artifact:
    donated state carry, a replicated [K, 2] int32 schedule TRAJECTORY
    scanned as xs (round k runs taus[k]), stacked [K] metrics tagged with
    the realized schedule."""
    arch = REGISTRY["qwen3-1.7b"]
    built = S.build_train_superstep(arch, "train_4k", mesh, rounds=2,
                                    tau1_max=3, tau2_max=2, reduced=True)
    assert built.meta["kind"] == "superstep"
    assert built.meta["rounds"] == 2 and built.meta["tau1_max"] == 3
    assert built.meta["schedule"] == "trajectory"
    taus_abs = built.args[-1]
    assert taus_abs.shape == (2, 2) and taus_abs.dtype == jnp.int32
    assert built.lower() is not None


def test_plan_train_schedule_roofline_measured(mesh):
    """use_roofline=True feeds the compiled local step's MEASURED FLOPs
    (and measured collective bytes, when the lowering has any) into the
    planner instead of the 6*P*tokens estimate."""
    arch = REGISTRY["qwen3-1.7b"]
    measured = S.roofline_cost_inputs(arch, "train_4k", mesh, reduced=True)
    assert measured["step_flops"] > 0
    # single-device host mesh mixes in registers: documented 0-collective
    # fallback to the analytic wire size
    assert measured["gossip_collective_bytes"] == 0.0
    p = S.plan_train_schedule(arch, "train_4k", mesh, budget_s=3600.0,
                              reduced=True, use_roofline=True)
    assert p.tau1 >= 1 and p.tau2 >= 1
    assert p.round_cost.time_s > 0


def test_reduced_decode_lowers(mesh):
    arch = REGISTRY["falcon-mamba-7b"]
    built = S.build_decode(arch, "decode_32k", mesh, reduced=True)
    assert built.lower() is not None


def test_reduced_prefill_lowers(mesh):
    arch = REGISTRY["seamless-m4t-medium"]
    built = S.build_prefill(arch, "prefill_32k", mesh, reduced=True)
    assert built.lower() is not None


def test_gossip_step_lowers_and_executes(mesh):
    arch = REGISTRY["granite-moe-1b-a400m"]
    built = S.build_gossip_step(arch, mesh, reduced=True)
    compiled = built.lower().compile()
    assert compiled is not None


def test_memory_tokens_scaling():
    from repro.configs.base import SHAPES

    audio = REGISTRY["seamless-m4t-medium"].model
    vlm = REGISTRY["llama-3.2-vision-90b"].model
    assert S.memory_tokens_for(audio, SHAPES["prefill_32k"]) == 32768 // 4
    assert S.memory_tokens_for(vlm, SHAPES["prefill_32k"]) == 4096


def test_batch_not_divisible_raises(mesh):
    """Global batch must cover the node count."""
    import dataclasses

    from repro.configs.base import InputShape
    arch = REGISTRY["qwen3-1.7b"]
    from repro.launch.sharding import num_nodes_for
    n = num_nodes_for(arch.sharding_mode, mesh, arch.fsdp_nodes)
    assert n >= 1  # on the 1x1 host mesh there's a single node — fine


def test_dryrun_runnable_combos_count():
    total = sum(len(a.shapes()) for a in REGISTRY.values())
    assert total == 36  # 44 assigned minus 8 documented long_500k skips
    skipped = sum(len(a.skip_shapes) for a in REGISTRY.values())
    assert skipped == 8
    for a in REGISTRY.values():
        if a.skip_shapes:
            assert a.skip_reason
