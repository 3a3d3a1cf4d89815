"""Every Mosaic kernel, compiled for a described v5e chip at a real width.

Interpret mode, which every other kernel test uses, cannot see what the TPU
compiler refuses: blocks not aligned to the tiling, more VMEM than a kernel
may hold. Here each op registered ``mosaic=True`` is compiled with
``interpret=False`` at qwen3-1.7b's MLP weight (2048 x 6144 bf16), for a
chip that is described, not attached: through its ``repro.kernels.ops``
wrapper, except the fused CHOCO-TopK kernel, which is compiled directly
against a threshold spec.
Nothing runs, so nothing here says anything about results or times.

The topology is described only inside a fixture, because loading the TPU
compiler takes a process-wide lock; keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import choco_fused, ops, registry

SHAPE = (2048, 6144)      # qwen3-1.7b d_model x d_ff
DTYPE = jnp.bfloat16
TOPK = SHAPE[0] * SHAPE[1] // 2   # TopK's default frac
# (the topk_mask case also compiles the TPU threshold select, the bisection
# over the magnitude bits that finds the threshold before the Mosaic mask
# kernel runs. The fused CHOCO-TopK kernel is compiled against a threshold
# spec instead.)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one.
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(sharding, shape=SHAPE, dtype=DTYPE):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cases(s):
    """op -> (fn over the specs, specs)."""
    x = _spec(s)
    noise = _spec(s, dtype=jnp.float32)
    gamma = _spec(s, (), jnp.float32)
    x2d = _spec(s, jax.eval_shape(lambda v: ops._to_2d(v)[0], x).shape)
    tile = _spec(s, (1, 1), jnp.float32)
    return {
        "qsgd_quantize": (
            lambda x, n: ops.qsgd_quantize(x, n, interpret=False),
            (x, noise)),
        "gossip_mix": (
            lambda x, nb, w: ops.gossip_mix(x, nb, w, interpret=False),
            (x, _spec(s, (2,) + SHAPE), _spec(s, (3,), jnp.float32))),
        "choco_move": (
            lambda x, y, my, g: ops.choco_move(x, y, my, g,
                                               interpret=False),
            (x, x, x, gamma)),
        "topk_mask": (
            lambda x: ops.top_k_compress(x, TOPK, interpret=False),
            (x,)),
        "choco_qsgd": (
            lambda x, y, my, g, n: ops.choco_qsgd_move(
                x, y, my, g, n, interpret=False),
            (x, x, x, gamma, noise)),
        "choco_topk": (
            lambda x, y, my, d, g, t: choco_fused.choco_topk_2d(
                x, y, my, d, g, t, interpret=False),
            (x2d, x2d, x2d, x2d, tile, _spec(s, (1, 1), DTYPE))),
    }


MOSAIC_OPS = sorted(op.name for op in registry.list_ops() if op.mosaic)


def test_every_mosaic_op_has_a_compile_case():
    assert sorted(_cases(None)) == MOSAIC_OPS


@pytest.mark.parametrize("name", MOSAIC_OPS)
def test_mosaic_op_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, specs = _cases(one_chip)[name]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the op streams its buffers through VMEM tiles: its scratch stays a
    # few buffers of the leaf, far below the chip's 16 GB.
    leaf = SHAPE[0] * SHAPE[1] * 4
    assert mem.temp_size_in_bytes < 8 * leaf
