"""The program's latent attention and held-expert layer against the plain
float32 references (``bench/reference/mla.py``, ``bench/reference/moe.py``)
at a small size with seeded weights, forward and gradients; the shares of
an expert-parallel layer add up to the whole layer; and routing that sends
every choice to a held expert drops none."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import mla as ref_mla
from bench.reference import moe as ref_moe
from bench.reference.model import Ops, make_weights
from repro.models import LayerSpec, ModelConfig
from repro.models import transformer as tf_lib

MODEL = {
    "d_model": 32, "num_heads": 4, "num_kv_heads": 4, "head_dim": 12,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "rope_theta": 50000.0, "rms_eps": 1e-5,
    "d_ff": 64, "vocab_size": 64, "num_experts": 64, "router_experts": 64,
    "experts_per_token": 6, "expert_d_ff": 16, "num_shared_experts": 2,
    "router_scoring": "sigmoid", "routed_scaling": 2.446,
    "router_aux_coef": 1e-4, "dtype": "float32",
}
SEQ = 16


def _cfg(**over):
    m = {**MODEL, **over}
    keys = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in m.items() if k in keys and k != "dtype"}
    return ModelConfig(name="t", arch_type="moe", num_layers=1,
                       dtype=jnp.float32, attn_q_chunk=8, attn_kv_chunk=8,
                       pattern=(LayerSpec(mixer="mla", ffn="moe"),), **kw)


def _weights(mod, model, seed=0):
    specs = {k: (s, "float32", init) for k, (s, _, init)
             in mod.params(model).items()}
    return {k: v.astype(jnp.float32) for k, v in
            make_weights(specs, model, jax.random.key(seed)).items()}


def _nest(flat):
    """Reference flat names (``mixer.wq``) -> the program's layer tree."""
    out = {}
    for k, v in flat.items():
        parts = k.split(".")
        node = out
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = v
    return out


def _program_layer(p, h, cfg, spec):
    positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    return tf_lib._apply_layer(p, spec, h, cfg, positions, None, False)


def _x(seed=1, shape=(2, SEQ, 32)):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.max(np.abs(b))), 1e-6)
    assert float(np.max(np.abs(a - b))) <= tol * scale, (
        float(np.max(np.abs(a - b))), scale)


def test_mla_matches_the_reference_forward_and_gradients():
    cfg = _cfg()
    spec = LayerSpec(mixer="mla", ffn="none")
    w = _weights(ref_mla, MODEL)
    ops = Ops("float32", MODEL["rms_eps"])
    pos = jnp.arange(SEQ, dtype=jnp.int32)
    h = _x()
    cot = _x(7)

    def prog(w, h):
        out, _ = _program_layer(_nest(w), h, cfg, spec)
        return jnp.sum(out * cot), out

    def ref(w, h):
        out = ref_mla.apply(w, h, pos, MODEL, ops)
        return jnp.sum(out * cot), out

    with jax.default_matmul_precision("highest"):
        (_, got), g_got = jax.value_and_grad(prog, (0, 1), has_aux=True)(w, h)
        (_, want), g_want = jax.value_and_grad(ref, (0, 1), has_aux=True)(w, h)
    _close(got, want)
    _close(g_got[1], g_want[1])
    for k in w:
        _close(g_got[0][k], g_want[0][k], 1e-4)


def _moe_program(w, h, cfg):
    spec = LayerSpec(mixer="mla", ffn="moe")
    p = _nest(w)
    h2, counters = tf_lib._ffn_block(p, spec, h, cfg)
    return h2, counters


def test_held_expert_layer_matches_the_reference_forward_and_gradients():
    model = {**MODEL, "num_experts": 16}
    cfg = _cfg(num_experts=16)
    w = _weights(ref_moe, model)
    ops = Ops("float32", MODEL["rms_eps"])
    pos = jnp.arange(SEQ, dtype=jnp.int32)
    h = _x()
    cot = _x(7)

    def prog(w, h):
        out, counters = _moe_program(w, h, cfg)
        aux = counters["aux"]
        return (jnp.sum(out * cot) + cfg.router_aux_coef * aux,
                (out, counters))

    def ref(w, h):
        out = ref_moe.apply(w, h, pos, model, ops)
        return jnp.sum(out * cot), out

    with jax.default_matmul_precision("highest"):
        (_, (got, counters)), g_got = jax.value_and_grad(
            prog, (0, 1), has_aux=True)(w, h)
        (_, want), g_want = jax.value_and_grad(ref, (0, 1),
                                               has_aux=True)(w, h)
    _close(got, want)
    _close(g_got[1], g_want[1])
    for k in w:
        _close(g_got[0][k], g_want[0][k], 1e-4)
    assert float(g_got[0]["ffn.router_bias"].sum()) == 0.0
    # 16 of 64 experts held: about a quarter of the 2 x 16 x 6 pairs
    assert 0 < float(counters["moe_rows"]) < 2 * SEQ * 6


@pytest.mark.parametrize("shares", [4])
def test_the_shares_of_an_expert_parallel_layer_add_up_to_the_whole(shares):
    """Share j holds experts 16j..16j+15 of 64: the router's and the
    bias's columns rotated by 16j, so that they are its first 16. The
    shares' outputs, with the shared experts (which every chip computes)
    counted once, add up to the uncut reference layer over all 64."""
    held = MODEL["router_experts"] // shares
    w = _weights(ref_moe, MODEL)
    w["ffn.router_bias"] = jax.random.normal(jax.random.key(3), (64,)) * 0.1
    ops = Ops("float32", MODEL["rms_eps"])
    h = _x()
    with jax.default_matmul_precision("highest"):
        whole = ref_moe.apply(w, h, jnp.arange(SEQ), MODEL, ops) - h
        cfg = _cfg(num_experts=held)
        parts, rows = [], 0.0
        for j in range(shares):
            wj = dict(w)
            wj["ffn.router"] = jnp.roll(w["ffn.router"], -held * j, axis=1)
            wj["ffn.router_bias"] = jnp.roll(w["ffn.router_bias"], -held * j)
            for k in ("ffn.w_gate", "ffn.w_up", "ffn.w_down"):
                wj[k] = w[k][held * j:held * (j + 1)]
            out, counters = _moe_program(wj, h, cfg)
            parts.append(out - h)
            rows += float(counters["moe_rows"])
        p = _nest(w)
        shared = jnp.zeros_like(h) + tf_lib.swiglu(
            tf_lib.rms_norm(h, p["ln2"], MODEL["rms_eps"]),
            p["ffn"]["shared"]["w_gate"], p["ffn"]["shared"]["w_up"],
            p["ffn"]["shared"]["w_down"])
    _close(sum(parts) - (shares - 1) * shared, whole)
    assert rows == 2 * SEQ * MODEL["experts_per_token"]


def test_a_bias_that_sends_every_choice_to_held_experts_drops_none():
    model = {**MODEL, "num_experts": 16}
    cfg = _cfg(num_experts=16)
    w = _weights(ref_moe, model)
    w["ffn.router_bias"] = jnp.where(jnp.arange(64) < 16, 10.0, 0.0)
    ops = Ops("float32", MODEL["rms_eps"])
    h = _x()
    with jax.default_matmul_precision("highest"):
        got, counters = _moe_program(w, h, cfg)
        want = ref_moe.apply(w, h, jnp.arange(SEQ), model, ops)
    # every (token, choice) pair is held: the static buffer is full
    assert float(counters["moe_rows"]) == 2 * SEQ * 6
    _close(got, want)
