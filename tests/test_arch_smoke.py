"""Per-architecture smoke tests: REDUCED variant of each assigned config
runs one forward/train step on CPU, asserting output shapes + no NaNs;
decode-capable archs also run a prefill + decode step."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY
from repro.models import (
    decode_step, init_params, prefill, train_loss,
)

ARCH_IDS = sorted(REGISTRY)


def _smoke_batch(cfg, b=2, s=32, key=jax.random.key(0)):
    from repro.models.common import pad_vocab

    ks = jax.random.split(key, 3)
    v = min(cfg.vocab_size, pad_vocab(cfg.vocab_size))
    batch = {
        "tokens": jax.random.randint(ks[0], (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (b, s), 0, cfg.vocab_size),
    }
    if cfg.has_memory_input:
        m = cfg.memory_tokens or 16
        batch["memory"] = jax.random.normal(
            ks[2], (b, m, cfg.memory_dim or cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_reduced_train_step(arch_id):
    arch = REGISTRY[arch_id]
    cfg = arch.reduced
    params, axes = init_params(cfg, jax.random.key(0))
    is_axes = lambda x: isinstance(x, tuple)
    n_params = len(jax.tree_util.tree_leaves(params))
    n_axes = len(jax.tree_util.tree_leaves(axes, is_leaf=is_axes))
    assert n_params == n_axes
    batch = _smoke_batch(cfg)

    loss, grads = jax.value_and_grad(
        lambda p: train_loss(p, batch, cfg))(params)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch_id}: non-finite loss"
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert bool(jnp.isfinite(g).all()), f"{arch_id}: NaN grad at {path}"


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_reduced_decode_roundtrip(arch_id):
    arch = REGISTRY[arch_id]
    cfg = arch.reduced
    params, _ = init_params(cfg, jax.random.key(0))
    batch = _smoke_batch(cfg, b=2, s=16)
    logits, state = prefill(params, batch, cfg, max_len=24)
    from repro.models.common import pad_vocab

    assert logits.shape == (2, pad_vocab(cfg.vocab_size))
    assert bool(jnp.isfinite(logits).all())
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32) % cfg.vocab_size
    for _ in range(3):
        logits, state = decode_step(params, state, tok, cfg)
        assert bool(jnp.isfinite(logits).all()), f"{arch_id}: NaN decode"
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32) % cfg.vocab_size
    assert int(state.position) == 19


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_matches_assignment(arch_id):
    """The FULL configs carry the exact assigned hyper-parameters."""
    m = REGISTRY[arch_id].model
    expected = {
        "granite-moe-1b-a400m": dict(num_layers=24, d_model=1024, num_heads=16,
                                     num_kv_heads=8, d_ff=512, vocab_size=49155,
                                     num_experts=32, experts_per_token=8),
        "llama-3.2-vision-90b": dict(num_layers=100, d_model=8192, num_heads=64,
                                     num_kv_heads=8, d_ff=28672,
                                     vocab_size=128256),
        "qwen3-1.7b": dict(num_layers=28, d_model=2048, num_heads=16,
                           num_kv_heads=8, d_ff=6144, vocab_size=151936,
                           qk_norm=True),
        "qwen3-8b": dict(num_layers=36, d_model=4096, num_heads=32,
                         num_kv_heads=8, d_ff=12288, vocab_size=151936,
                         qk_norm=True),
        "gemma3-4b": dict(num_layers=34, d_model=2560, num_heads=8,
                          num_kv_heads=4, d_ff=10240, vocab_size=262144),
        "seamless-m4t-medium": dict(num_layers=12, encoder_layers=12,
                                    d_model=1024, num_heads=16,
                                    num_kv_heads=16, d_ff=4096,
                                    vocab_size=256206),
        "falcon-mamba-7b": dict(num_layers=64, d_model=4096, d_ff=0,
                                vocab_size=65024, ssm_state=16),
        "jamba-1.5-large-398b": dict(num_layers=72, d_model=8192, num_heads=64,
                                     num_kv_heads=8, d_ff=24576,
                                     vocab_size=65536, num_experts=16,
                                     experts_per_token=2, ssm_state=16),
        "deepseek-coder-33b": dict(num_layers=62, d_model=7168, num_heads=56,
                                   num_kv_heads=8, d_ff=19200,
                                   vocab_size=32256),
        "phi3.5-moe-42b-a6.6b": dict(num_layers=32, d_model=4096, num_heads=32,
                                     num_kv_heads=8, d_ff=6400,
                                     vocab_size=32064, num_experts=16,
                                     experts_per_token=2),
    }[arch_id]
    for k, v in expected.items():
        assert getattr(m, k) == v, f"{arch_id}.{k}: {getattr(m, k)} != {v}"
    assert m.citation, f"{arch_id} missing source citation"


@pytest.mark.parametrize(
    "arch_id", [a for a in ARCH_IDS if REGISTRY[a].chip_share is not None])
def test_chip_share_keeps_published_widths(arch_id):
    """A chip share differs from the published config only in the keys it
    lists under ``reduced`` (and its name), and states its deployment."""
    import dataclasses

    arch = REGISTRY[arch_id]
    share = arch.chip_share
    changed = {f.name for f in dataclasses.fields(arch.model)
               if getattr(share.model, f.name) != getattr(arch.model, f.name)}
    assert changed - {"name"} == set(share.reduced)
    assert share.source and share.deployment
    assert share.model.num_layers % share.model.period == 0


def test_train_no_reduced_needs_a_chip_share():
    from repro.launch import train

    arch_id = next(a for a in ARCH_IDS if REGISTRY[a].chip_share is None)
    with pytest.raises(SystemExit, match="no chip-share config"):
        train.main(["--arch", arch_id, "--no-reduced", "--rounds", "1"])


def test_gemma3_pattern_is_5_local_1_global():
    m = REGISTRY["gemma3-4b"].model
    globals_ = [i for i, s in enumerate(m.layer_specs()) if s.window == 0]
    assert globals_ == [5, 11, 17, 23, 29]


def test_jamba_pattern_interleave():
    m = REGISTRY["jamba-1.5-large-398b"].model
    specs = m.layer_specs()
    attn = [i for i, s in enumerate(specs) if s.mixer == "attn"]
    assert attn == list(range(4, 72, 8))            # 1:7 interleave, offset 4
    moe = [i for i, s in enumerate(specs) if s.ffn == "moe"]
    assert moe == list(range(1, 72, 2))             # MoE every 2, offset 1


def test_llama_vision_cross_every_5th():
    m = REGISTRY["llama-3.2-vision-90b"].model
    cross = [i for i, s in enumerate(m.layer_specs()) if s.cross_attn]
    assert cross == list(range(4, 100, 5))
