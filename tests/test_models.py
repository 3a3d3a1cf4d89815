"""Model-zoo correctness: decode/forward consistency, attention variants,
mamba scan equivalence, MoE vs dense reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (
    LayerSpec, ModelConfig, forward, init_params, prefill, decode_step,
)
from repro.models.transformer import _unembed

KW = dict(dtype=jnp.float32, attn_q_chunk=8, attn_kv_chunk=8,
          loss_seq_chunk=8, ssm_chunk=4)


def _dense_cfg(**over):
    base = dict(name="t", arch_type="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                vocab_size=128, **KW)
    base.update(over)
    return ModelConfig(**base)


@pytest.mark.parametrize("cfg", [
    _dense_cfg(qk_norm=True),
    _dense_cfg(pattern=(LayerSpec(window=8), LayerSpec())),
    ModelConfig(name="ssm", arch_type="ssm", num_layers=2, d_model=64,
                num_heads=0, num_kv_heads=0, head_dim=0, d_ff=0,
                vocab_size=128, ssm_state=8,
                pattern=(LayerSpec(mixer="mamba", ffn="none"),), **KW),
    ModelConfig(name="moe-nodrop", arch_type="moe", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
                vocab_size=128, num_experts=4, experts_per_token=2, **KW),
    ModelConfig(name="mla-moe", arch_type="moe", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=4, head_dim=24, d_ff=128,
                vocab_size=128, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
                router_experts=8, experts_per_token=3, expert_d_ff=32,
                num_shared_experts=2, router_scoring="sigmoid",
                routed_scaling=2.446, rms_eps=1e-5,
                pattern=(LayerSpec(mixer="mla", ffn="mlp"),
                         LayerSpec(mixer="mla", ffn="moe")), **KW),
], ids=["qknorm", "window", "mamba", "moe", "mla"])
def test_decode_matches_forward(cfg):
    """prefill(s) + decode(s+1) logits == full forward logits."""
    params, _ = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 12), 0, 128)
    h, _ = forward(params, toks, cfg, checkpoint=False)
    full = _unembed(params, h, cfg)
    lg_pre, st = prefill(params, {"tokens": toks[:, :11]}, cfg, max_len=16)
    np.testing.assert_allclose(np.asarray(lg_pre), np.asarray(full[:, 10]),
                               rtol=2e-4, atol=2e-4)
    lg_dec, _ = decode_step(params, st, toks[:, 11:12], cfg)
    np.testing.assert_allclose(np.asarray(lg_dec), np.asarray(full[:, 11]),
                               rtol=2e-4, atol=2e-4)


def test_sliding_window_restricts_attention():
    """A token beyond the window cannot influence the output."""
    cfg = _dense_cfg(num_layers=2, pattern=(LayerSpec(window=4),))
    params, _ = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 16), 0, 128)
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % 128)  # mutate pos 0
    h1, _ = forward(params, toks, cfg, checkpoint=False)
    h2, _ = forward(params, toks2, cfg, checkpoint=False)
    # position 15 is > window*layers away only if 0 outside receptive field:
    # receptive field = 2 layers * (4-1) = 6; pos 15 unaffected.
    np.testing.assert_allclose(np.asarray(h1[0, 15]), np.asarray(h2[0, 15]),
                               atol=1e-5)
    # a nearby position IS affected.
    assert float(jnp.max(jnp.abs(h1[0, 2] - h2[0, 2]))) > 1e-6


def test_causality():
    """Future tokens never influence past positions."""
    cfg = _dense_cfg()
    params, _ = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 16), 0, 128)
    toks2 = toks.at[0, 10].set((toks[0, 10] + 1) % 128)
    h1, _ = forward(params, toks, cfg, checkpoint=False)
    h2, _ = forward(params, toks2, cfg, checkpoint=False)
    np.testing.assert_allclose(np.asarray(h1[0, :10]), np.asarray(h2[0, :10]),
                               atol=1e-5)


def test_mamba_chunked_equals_unchunked():
    """The chunked associative scan equals a single-chunk scan."""
    from repro.models import mamba as M

    cfg_small = ModelConfig(name="s", arch_type="ssm", num_layers=1,
                            d_model=32, num_heads=0, num_kv_heads=0,
                            head_dim=0, d_ff=0, vocab_size=64, ssm_state=4,
                            **{**KW, "ssm_chunk": 4})
    cfg_big = ModelConfig(name="s", arch_type="ssm", num_layers=1,
                          d_model=32, num_heads=0, num_kv_heads=0,
                          head_dim=0, d_ff=0, vocab_size=64, ssm_state=4,
                          **{**KW, "ssm_chunk": 16})
    from repro.models.common import ParamFactory, split_annotations
    f = ParamFactory(jax.random.key(0), jnp.float32)
    p, _ = split_annotations(M.mamba_params(f, cfg_small))
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    y1 = M.mamba_mixer(p, x, cfg_small)
    y2 = M.mamba_mixer(p, x, cfg_big)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-5)


def test_mamba_scan_matches_sequential_decode():
    """Running the full-seq mixer equals stepping the recurrence token by
    token (the decode path)."""
    from repro.models import mamba as M
    from repro.models.common import ParamFactory, split_annotations

    cfg = ModelConfig(name="s", arch_type="ssm", num_layers=1, d_model=32,
                      num_heads=0, num_kv_heads=0, head_dim=0, d_ff=0,
                      vocab_size=64, ssm_state=4, **KW)
    f = ParamFactory(jax.random.key(0), jnp.float32)
    p, _ = split_annotations(M.mamba_params(f, cfg))
    x = jax.random.normal(jax.random.key(1), (1, 8, 32))
    y_full = M.mamba_mixer(p, x, cfg)
    state = M.init_mamba_state(cfg, 1)
    outs = []
    for t in range(8):
        y, state = M.mamba_decode(p, x[:, t:t + 1], cfg, state)
        outs.append(y)
    y_step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_step),
                               rtol=1e-4, atol=1e-5)


def _moe_cfg(**over):
    base = dict(name="m", arch_type="moe", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
                vocab_size=128, num_experts=4, experts_per_token=2, **KW)
    base.update(over)
    return ModelConfig(**base)


def _dense_moe(pm, x, k, held):
    """Softmax top-k over every router expert, renormalised; the held
    experts' SwiGLU computed densely and weighted."""
    probs = jax.nn.softmax(x @ pm["router"], -1)
    gv, gi = jax.lax.top_k(probs, k)
    gv = gv / gv.sum(-1, keepdims=True)
    ref = jnp.zeros_like(x)
    for e in range(held):
        h = jax.nn.silu(x @ pm["w_gate"][e]) * (x @ pm["w_up"][e])
        w = ((gi == e) * gv).sum(-1)
        ref = ref + w[..., None] * (h @ pm["w_down"][e])
    return ref


def test_moe_matches_dense_reference_no_drops():
    from repro.models.moe import moe_ffn

    cfg = _moe_cfg()
    params, _ = init_params(cfg, jax.random.key(0))
    pm = {k: v[0] for k, v in params["blocks"][0]["ffn"].items()}
    x = jax.random.normal(jax.random.key(5), (2, 16, 64))
    out, aux, rows = moe_ffn(pm, x, cfg)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_moe(pm, x, 2, 4)),
                               rtol=1e-4, atol=1e-4)
    assert float(aux) > 0
    assert float(rows) == 2 * 16 * 2        # every pair held


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_moe_balance_loss_follows_the_router_scoring(scoring):
    """A softmax router keeps the Switch Transformer's load-balance loss
    over the whole batch (E sum_i f_i P_i, f_i the share of first choices);
    a sigmoid router takes DeepSeek-V3's sequence-wise loss over all k
    choices."""
    from repro.models.moe import route

    cfg = _moe_cfg(router_scoring=scoring)
    params, _ = init_params(cfg, jax.random.key(0))
    pm = {k: v[0] for k, v in params["blocks"][0]["ffn"].items()}
    x = jax.random.normal(jax.random.key(5), (2, 16, 64))
    idx, _, aux = route(pm, x, cfg)
    logits = np.asarray(x @ pm["router"], np.float64)
    e, k = 4, 2
    if scoring == "softmax":
        probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        first = np.eye(e)[probs.argmax(-1)]
        want = e * np.sum(first.mean((0, 1)) * probs.mean((0, 1)))
    else:
        scores = 1 / (1 + np.exp(-logits))
        chosen = np.eye(e)[np.asarray(idx)].sum(2)
        f = chosen.sum(1) * e / (k * 16)
        p = (scores / scores.sum(-1, keepdims=True)).mean(1)
        want = np.mean(np.sum(f * p, -1))
    np.testing.assert_allclose(float(aux), want, rtol=1e-5)


def test_moe_routing_to_held_experts_drops_no_token():
    """Dropless: however unevenly the router sends tokens, every pair
    routed to a held expert is computed. A bias that sends all of them
    to experts 0 and 1 fills two groups with every token; the output is
    the dense reference's, and experts held elsewhere add nothing."""
    from repro.models.moe import moe_ffn

    cfg = _moe_cfg(num_experts=2, router_experts=4)
    params, _ = init_params(_moe_cfg(), jax.random.key(0))
    pm = {k: v[0] for k, v in params["blocks"][0]["ffn"].items()}
    x = jax.random.normal(jax.random.key(5), (2, 64, 64))
    held = {k: (v[:2] if k.startswith("w_") else v) for k, v in pm.items()}
    skewed = dict(held, router_bias=jnp.array([9.0, 9.0, 0.0, 0.0]))
    out, _, rows = moe_ffn(skewed, x, cfg)
    assert float(rows) == 2 * 64 * 2
    assert bool(jnp.all(jnp.any(out != 0, -1)))
    probs = jax.nn.softmax(x @ pm["router"], -1)
    gv = probs[..., :2] / probs[..., :2].sum(-1, keepdims=True)
    ref = jnp.zeros_like(x)
    for e in range(2):
        h = jax.nn.silu(x @ pm["w_gate"][e]) * (x @ pm["w_up"][e])
        ref = ref + gv[..., e:e + 1] * (h @ pm["w_down"][e])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)
    # unbiased, the pairs routed to the two experts held elsewhere are left
    # out: the output is the dense reference's over the held two
    out, _, rows = moe_ffn(held, x, cfg)
    assert float(rows) < 2 * 64 * 2
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_moe(pm, x, 2, 2)),
                               rtol=1e-4, atol=1e-4)


def test_train_loss_counts_held_pairs_and_reports_the_cross_entropy():
    """The loss's counters carry the held (token, expert) pairs of every
    MoE layer; the balance loss moves the router's gradient and not the
    loss's value; a dense model has no counters."""
    from repro.models import train_loss

    cfg = _moe_cfg(num_experts=2, router_experts=4)
    params, _ = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    batch = {"tokens": toks, "labels": toks}
    (loss, counters), g = jax.value_and_grad(
        lambda p: train_loss(p, batch, cfg), has_aux=True)(params)
    assert set(counters) == {"moe_rows"}
    assert 0 < float(counters["moe_rows"]) <= 2 * 2 * 16 * 2
    off = dataclasses.replace(cfg, router_aux_coef=0.0)
    (loss0, _), g0 = jax.value_and_grad(
        lambda p: train_loss(p, batch, off), has_aux=True)(params)
    assert float(loss) == float(loss0)
    router = lambda t: t["blocks"][0]["ffn"]["router"]
    assert float(jnp.max(jnp.abs(router(g) - router(g0)))) > 0
    dense = _dense_cfg()
    dp, _ = init_params(dense, jax.random.key(0))
    assert train_loss(dp, batch, dense)[1] == {}


def test_checkpointed_forward_matches_uncheckpointed():
    cfg = _dense_cfg()
    params, _ = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    h1, _ = forward(params, toks, cfg, checkpoint=False)
    h2, _ = forward(params, toks, cfg, checkpoint=True)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5,
                               atol=1e-5)
