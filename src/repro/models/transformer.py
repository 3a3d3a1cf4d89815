"""The generic decoder / encoder-decoder stack over LayerSpec patterns.

One code path serves all 10 assigned architectures: the per-arch config
chooses the repeating ``pattern`` of layers (attn/mamba mixer, mlp/moe FFN,
sliding windows, cross-attention) and the stack scans over pattern periods
with stacked parameters (``lax.scan`` keeps HLO size independent of depth —
a 100-layer model compiles as fast as a 2-layer one).

Entry points:
  init_params(cfg, key)                      -> (params, logical_axes)
  train_loss(params, batch, cfg, rng)        -> (scalar loss, counters)
  prefill(params, batch, cfg)                -> (last_logits, DecodeState)
  decode_step(params, state, tokens, cfg)    -> (logits, DecodeState)
  init_decode_state(cfg, batch, max_len)     -> DecodeState (zeros/abstract)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.policy import shard_hidden
from repro.models.common import (
    LayerSpec,
    ModelConfig,
    ParamFactory,
    pad_vocab,
    rms_norm,
    split_annotations,
    swiglu,
)

PyTree = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _mlp_params(f: ParamFactory, cfg: ModelConfig) -> Dict:
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "w_gate": f.dense((d, ff), ("embed", "mlp")),
        "w_up": f.dense((d, ff), ("embed", "mlp")),
        "w_down": f.dense((ff, d), ("mlp", "embed")),
    }


def _layer_params(f: ParamFactory, cfg: ModelConfig, spec: LayerSpec) -> Dict:
    p: Dict[str, Any] = {"ln1": f.zeros((cfg.d_model,), ("embed",))}
    if spec.mixer == "attn":
        p["mixer"] = attn_lib.attn_params(f, cfg)
    elif spec.mixer == "mla":
        p["mixer"] = attn_lib.mla_params(f, cfg)
    elif spec.mixer == "mamba":
        p["mixer"] = mamba_lib.mamba_params(f, cfg)
    else:
        raise ValueError(f"unknown mixer {spec.mixer!r}")
    if spec.cross_attn:
        p["ln_cross"] = f.zeros((cfg.d_model,), ("embed",))
        p["cross"] = attn_lib.attn_params(f, cfg, cross=True)
    if spec.ffn == "mlp":
        p["ln2"] = f.zeros((cfg.d_model,), ("embed",))
        p["ffn"] = _mlp_params(f, cfg)
    elif spec.ffn == "moe":
        p["ln2"] = f.zeros((cfg.d_model,), ("embed",))
        p["ffn"] = moe_lib.moe_params(f, cfg)
    elif spec.ffn != "none":
        raise ValueError(f"unknown ffn {spec.ffn!r}")
    return p


def _stack(trees: List[PyTree]) -> PyTree:
    """Stack a list of identical-structure param trees along a new axis 0,
    prepending the 'layers' logical axis to each Annotated leaf."""
    from repro.models.common import Annotated

    is_ann = lambda x: isinstance(x, Annotated)

    def stack_leaf(*leaves):
        vals = [l.value for l in leaves]
        axes = ("layers",) + leaves[0].axes
        if isinstance(vals[0], jax.ShapeDtypeStruct):
            v = jax.ShapeDtypeStruct((len(vals),) + vals[0].shape, vals[0].dtype)
        else:
            v = jnp.stack(vals)
        return Annotated(v, axes)

    return jax.tree_util.tree_map(stack_leaf, *trees, is_leaf=is_ann)


def init_params(cfg: ModelConfig, key: jax.Array, abstract: bool = False):
    """Returns (params, logical_axes) trees."""
    f = ParamFactory(key, cfg.dtype, abstract=abstract)
    v = pad_vocab(cfg.vocab_size)
    tree: Dict[str, Any] = {
        "embed": f.dense((v, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "final_norm": f.zeros((cfg.d_model,), ("embed",)),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = f.dense((cfg.d_model, v), ("embed", "vocab"))

    if cfg.has_memory_input:
        mem_dim = cfg.memory_dim or cfg.d_model
        tree["mem_proj"] = f.dense((mem_dim, cfg.d_model), (None, "embed"))

    if cfg.is_enc_dec:
        enc_spec = LayerSpec(mixer="attn", ffn="mlp")
        assert cfg.encoder_layers >= 1
        tree["encoder"] = _stack(
            [_layer_params(f, cfg, enc_spec) for _ in range(cfg.encoder_layers)]
        )
        tree["encoder_norm"] = f.zeros((cfg.d_model,), ("embed",))

    period_blocks = []
    for spec in cfg.pattern:
        period_blocks.append(_layer_params(f, cfg, spec))
    # one stacked tree per position in the period; stacked over num_periods.
    stacked = []
    for pos, spec in enumerate(cfg.pattern):
        copies = [period_blocks[pos]] + [
            _layer_params(f, cfg, spec) for _ in range(cfg.num_periods - 1)
        ]
        stacked.append(_stack(copies))
    tree["blocks"] = stacked

    return split_annotations(tree)


# ---------------------------------------------------------------------------
# Forward (training / evaluation)
# ---------------------------------------------------------------------------


def _encode_memory(params: Dict, memory: jnp.ndarray, cfg: ModelConfig,
                   checkpoint: bool) -> jnp.ndarray:
    """VLM: project frontend embeddings. Audio enc-dec: project then run the
    bidirectional encoder stack."""
    mem = jnp.einsum(
        "bmd,de->bme", memory.astype(cfg.dtype), params["mem_proj"].astype(cfg.dtype)
    )
    if not cfg.is_enc_dec:
        return mem
    positions = jnp.arange(mem.shape[1], dtype=jnp.int32)
    enc_spec = LayerSpec(mixer="attn", ffn="mlp")

    def enc_layer(h, layer_p):
        h = h + attn_lib.self_attention(
            layer_p["mixer"], rms_norm(h, layer_p["ln1"], cfg.rms_eps), cfg,
            enc_spec,
            positions=positions, checkpoint=checkpoint, causal=False)
        h = h + swiglu(rms_norm(h, layer_p["ln2"], cfg.rms_eps),
                       layer_p["ffn"]["w_gate"],
                       layer_p["ffn"]["w_up"], layer_p["ffn"]["w_down"])
        return shard_hidden(h), None

    body = jax.checkpoint(enc_layer) if checkpoint else enc_layer
    mem, _ = jax.lax.scan(body, shard_hidden(mem), params["encoder"])
    return rms_norm(mem, params["encoder_norm"], cfg.rms_eps)


def _ffn_block(layer_p: Dict, spec: LayerSpec, h: jnp.ndarray,
               cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """The layer's feed-forward sub-block; returns (h, its counters: the
    MoE balance loss ``aux`` and held (token, expert) pairs ``moe_rows``,
    none for a dense one)."""
    if spec.ffn == "none":
        return h, {}
    x2 = rms_norm(h, layer_p["ln2"], cfg.rms_eps)
    if spec.ffn == "mlp":
        return h + swiglu(x2, layer_p["ffn"]["w_gate"], layer_p["ffn"]["w_up"],
                          layer_p["ffn"]["w_down"]), {}
    with jax.named_scope("model.moe"):
        out, aux, rows = moe_lib.moe_ffn(layer_p["ffn"], x2, cfg)
    return h + out, {"aux": aux, "moe_rows": rows}


def _apply_layer(
    layer_p: Dict,
    spec: LayerSpec,
    h: jnp.ndarray,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    memory: Optional[jnp.ndarray],
    checkpoint: bool,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    x = rms_norm(h, layer_p["ln1"], cfg.rms_eps)
    if spec.mixer == "attn":
        mixed = attn_lib.self_attention(
            layer_p["mixer"], x, cfg, spec, positions=positions,
            checkpoint=checkpoint)
    elif spec.mixer == "mla":
        with jax.named_scope("model.mla"):
            mixed = attn_lib.self_attention(
                layer_p["mixer"], x, cfg, spec, positions=positions,
                checkpoint=checkpoint)
    else:
        mixed = mamba_lib.mamba_mixer(layer_p["mixer"], x, cfg,
                                      checkpoint=checkpoint)
    h = h + mixed
    if spec.cross_attn:
        assert memory is not None, f"{cfg.name}: cross-attn layer needs memory"
        xc = rms_norm(h, layer_p["ln_cross"], cfg.rms_eps)
        h = h + attn_lib.cross_attention(layer_p["cross"], xc, memory, cfg,
                                         checkpoint=checkpoint)
    return _ffn_block(layer_p, spec, h, cfg)


def forward(
    params: Dict,
    tokens: jnp.ndarray,            # [B, S]
    cfg: ModelConfig,
    *,
    memory: Optional[jnp.ndarray] = None,  # [B, M, mem_dim]
    checkpoint: Optional[bool] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (hidden [B,S,D], counters summed over layers: ``aux``, the
    MoE balance loss, and, for a model with MoE layers, ``moe_rows``).

    Under ``checkpoint`` each layer is rematerialised in the backward pass:
    a one-layer period as the scan's body, a longer one layer by layer."""
    checkpoint = cfg.remat if checkpoint is None else checkpoint
    h = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    mem = None
    if cfg.has_memory_input:
        assert memory is not None, f"{cfg.name} requires memory input"
        mem = _encode_memory(params, memory, cfg, checkpoint)
    zero = jnp.zeros((), jnp.float32)
    counters = {"aux": zero}
    if any(spec.ffn == "moe" for spec in cfg.pattern):
        counters["moe_rows"] = zero
    layer_remat = checkpoint and cfg.period > 1
    apply = functools.partial(_apply_layer, cfg=cfg, positions=positions,
                              memory=mem, checkpoint=checkpoint)
    if layer_remat:
        apply = jax.checkpoint(apply, static_argnums=(1,))

    def period_body(carry, period_params):
        h, counters = carry
        for pos, spec in enumerate(cfg.pattern):
            h, layer_counters = apply(period_params[pos], spec, h)
            counters = {k: v + layer_counters[k] if k in layer_counters
                        else v for k, v in counters.items()}
        return (shard_hidden(h), counters), None

    body = (jax.checkpoint(period_body) if checkpoint and not layer_remat
            else period_body)
    (h, counters), _ = jax.lax.scan(
        body, (shard_hidden(h), counters), tuple(params["blocks"]))
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    return h, counters


def _unembed(params: Dict, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        w = params["embed"].astype(h.dtype)  # [V, D]
        return jnp.einsum("...d,vd->...v", h, w)
    return jnp.einsum("...d,dv->...v", h, params["lm_head"].astype(h.dtype))


def train_loss(
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    rng: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """(Next-token cross-entropy, the model's counters). The cross-entropy
    is chunked over the sequence so the full [B,S,V] logit tensor never
    materializes. The MoE balance loss, times ``router_aux_coef``, is in
    the gradient and not in the value. A model with MoE layers counts
    ``moe_rows``; otherwise the counters are empty."""
    del rng
    tokens = batch["tokens"]
    labels = batch["labels"]
    h, counters = forward(params, tokens, cfg, memory=batch.get("memory"))
    aux = counters.pop("aux")
    aux = aux - jax.lax.stop_gradient(aux)
    h = shard_hidden(h)
    b, s, d = h.shape
    v = pad_vocab(cfg.vocab_size)
    chunk = cfg.loss_seq_chunk
    while s % chunk:
        chunk -= 1
    n = s // chunk
    hc = h.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def chunk_loss(carry, inp):
        hblk, lblk = inp
        hblk = shard_hidden(hblk)
        logits = _unembed(params, hblk, cfg).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lblk[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(logz - gold), None

    body = jax.checkpoint(chunk_loss) if cfg.remat else chunk_loss
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
    loss = total / (b * s)
    return loss + cfg.router_aux_coef * aux, counters


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


class DecodeState(NamedTuple):
    caches: Tuple[PyTree, ...]      # per period position, stacked over periods
    memory: Optional[jnp.ndarray]   # encoder output / projected patches
    position: jnp.ndarray           # scalar int32: next position to write


def init_decode_state(
    cfg: ModelConfig, batch: int, max_len: int, abstract: bool = False
) -> DecodeState:
    caches = []
    for spec in cfg.pattern:
        if spec.mixer in ("attn", "mla"):
            one = attn_lib.init_kv_cache(cfg, spec, batch, max_len, abstract)
        else:
            one = mamba_lib.init_mamba_state(cfg, batch, abstract)

        def stack_leaf(x):
            if abstract:
                return jax.ShapeDtypeStruct((cfg.num_periods,) + x.shape, x.dtype)
            return jnp.broadcast_to(x[None], (cfg.num_periods,) + x.shape)

        caches.append(jax.tree_util.tree_map(stack_leaf, one))
    mem = None
    if cfg.has_memory_input:
        m = cfg.memory_tokens or 256
        shape = (batch, m, cfg.d_model)
        mem = (jax.ShapeDtypeStruct(shape, cfg.dtype) if abstract
               else jnp.zeros(shape, cfg.dtype))
    pos = (jax.ShapeDtypeStruct((), jnp.int32) if abstract
           else jnp.zeros((), jnp.int32))
    return DecodeState(caches=tuple(caches), memory=mem, position=pos)


def prefill(
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    max_len: int,
) -> Tuple[jnp.ndarray, DecodeState]:
    """Process the prompt; returns (logits of last token [B,V], state)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    h = params["embed"].astype(cfg.dtype)[tokens]
    mem = None
    if cfg.has_memory_input:
        mem = _encode_memory(params, batch["memory"], cfg, checkpoint=False)

    # Scan periods; within a period iterate positions (python loop).
    def scan_body(h, period_params):
        caches_out = []
        for pos_idx, spec in enumerate(cfg.pattern):
            layer_p = period_params[pos_idx]
            x = rms_norm(h, layer_p["ln1"], cfg.rms_eps)
            if spec.mixer in ("attn", "mla"):
                cache0 = attn_lib.init_kv_cache(cfg, spec, b, max_len)
                mixed, cache = attn_lib.prefill_attention(
                    layer_p["mixer"], x, cfg, spec, cache0, positions=positions)
            else:
                mixed, cache = mamba_lib.mamba_mixer(
                    layer_p["mixer"], x, cfg, return_state=True)
            h = h + mixed
            if spec.cross_attn:
                xc = rms_norm(h, layer_p["ln_cross"], cfg.rms_eps)
                h = h + attn_lib.cross_attention(layer_p["cross"], xc, mem, cfg)
            h, _ = _ffn_block(layer_p, spec, h, cfg)
            caches_out.append(cache)
        return shard_hidden(h), tuple(caches_out)

    h, caches = jax.lax.scan(scan_body, shard_hidden(h),
                             tuple(params["blocks"]))
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    last_logits = _unembed(params, h[:, -1], cfg)
    state = DecodeState(
        caches=caches, memory=mem,
        position=jnp.asarray(s, jnp.int32))
    return last_logits, state


def decode_step(
    params: Dict,
    state: DecodeState,
    tokens: jnp.ndarray,            # [B, 1]
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, DecodeState]:
    """One-token decode against the KV cache / SSM state."""
    h = params["embed"].astype(cfg.dtype)[tokens]
    position = state.position
    mem = state.memory

    def scan_body(h, inp):
        period_params, caches = inp
        caches_out = []
        for pos_idx, spec in enumerate(cfg.pattern):
            layer_p = period_params[pos_idx]
            cache = caches[pos_idx]
            x = rms_norm(h, layer_p["ln1"], cfg.rms_eps)
            if spec.mixer in ("attn", "mla"):
                mixed, cache = attn_lib.decode_attention(
                    layer_p["mixer"], x, cfg, spec, cache, position=position)
            else:
                mixed, cache = mamba_lib.mamba_decode(layer_p["mixer"], x, cfg, cache)
            h = h + mixed
            if spec.cross_attn:
                xc = rms_norm(h, layer_p["ln_cross"], cfg.rms_eps)
                h = h + attn_lib.cross_attention(layer_p["cross"], xc, mem, cfg)
            h, _ = _ffn_block(layer_p, spec, h, cfg)
            caches_out.append(cache)
        return h, tuple(caches_out)

    h, new_caches = jax.lax.scan(
        scan_body, h, (tuple(params["blocks"]), state.caches))
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    logits = _unembed(params, h[:, -1], cfg)
    new_state = DecodeState(
        caches=new_caches, memory=mem, position=position + 1)
    return logits, new_state
