"""GQA and latent (MLA) attention: chunked (flash-style) training/prefill
path + KV-cache decode path, with sliding windows, qk-norm, RoPE and
cross-attention.

The chunked path never materializes the full [S, T] score matrix: it scans
query chunks (optionally ``jax.checkpoint``ed so the backward pass recomputes
tiles — flash-attention's memory behaviour, expressed in pure jnp so the
same code serves CPU tests and the TPU dry-run).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (
    Annotated,
    LayerSpec,
    ModelConfig,
    ParamFactory,
    rms_norm,
    rope,
    softcap,
)

NEG_INF = -1e9


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_params(f: ParamFactory, cfg: ModelConfig, cross: bool = False) -> Dict:
    h_ax = "heads" if cfg.attn_shard == "heads" else None
    kv_ax = "kv_heads" if cfg.attn_shard == "heads" else None
    hd_ax = "head_dim" if cfg.attn_shard == "head_dim" else None
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": f.dense((d, h, hd), ("embed", h_ax, hd_ax)),
        "wk": f.dense((d, kvh, hd), ("embed", kv_ax, hd_ax)),
        "wv": f.dense((d, kvh, hd), ("embed", kv_ax, hd_ax)),
        "wo": f.dense((h, hd, d), (h_ax, hd_ax, "embed")),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = f.zeros((hd,), (None,))
        p["k_norm"] = f.zeros((hd,), (None,))
    return p


def mla_params(f: ParamFactory, cfg: ModelConfig) -> Dict:
    """DeepSeek-V3 latent attention without q compression (q_lora_rank
    null): a plain q projection, the compressed kv and the shared rope key
    from one projection, the kv norm, and the kv up-projection."""
    h_ax = "heads" if cfg.attn_shard == "heads" else None
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope_d, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)
    return {
        "wq": f.dense((d, h, nope + rope_d), ("embed", h_ax, None)),
        "wkv_a": f.dense((d, r + rope_d), ("embed", None)),
        "kv_norm": f.zeros((r,), (None,)),
        "wkv_b": f.dense((r, h, nope + vd), (None, h_ax, None)),
        "wo": f.dense((h, vd, d), (h_ax, None, "embed")),
    }


# ---------------------------------------------------------------------------
# Chunked attention core
# ---------------------------------------------------------------------------


def _pick_chunk(total: int, want: int) -> int:
    """Largest divisor of ``total`` that is <= want (>=1)."""
    c = min(want, total)
    while total % c:
        c -= 1
    return c


def chunked_attention(
    q: jnp.ndarray,                 # [B, S, H, hd]
    k: jnp.ndarray,                 # [B, T, KVH, hd]
    v: jnp.ndarray,                 # [B, T, KVH, vd]
    *,
    q_positions: jnp.ndarray,       # [S] absolute positions of queries
    kv_positions: jnp.ndarray,      # [T] absolute positions of keys (-1 = empty)
    causal: bool = True,
    window: int = 0,
    cap: float = 0.0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    checkpoint: bool = False,
) -> jnp.ndarray:
    b, s, h, hd = q.shape
    t, kvh, vd = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kvh
    qc = _pick_chunk(s, q_chunk)
    kc = _pick_chunk(t, kv_chunk)
    nq, nk = s // qc, t // kc
    scale = hd ** -0.5

    if nq == 1 and nk == 1:
        # single-block path (decode / short prefill): no chunk reshapes —
        # keeps a sharded KV sequence dim intact (GSPMD reduces the softmax
        # over the sharded axis instead of resharding dynamic slices).
        qr1 = q.reshape(b, s, kvh, g, hd)
        s_ = jnp.einsum("bqngd,bknd->bngqk", qr1, k,
                        preferred_element_type=jnp.float32) * scale
        s_ = softcap(s_, cap)
        valid = kv_positions[None, :] >= 0
        if causal:
            valid = valid & (kv_positions[None, :] <= q_positions[:, None])
        if window > 0:
            valid = valid & (kv_positions[None, :] >
                             q_positions[:, None] - window)
        s_ = jnp.where(valid[None, None, None], s_, NEG_INF)
        m = jnp.max(s_, axis=-1, keepdims=True)
        p = jnp.exp(s_ - m)
        p = jnp.where(valid[None, None, None], p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out1 = jnp.einsum("bngqk,bknd->bngqd", p, v,
                          preferred_element_type=jnp.float32)
        out1 = out1 / jnp.maximum(l, 1e-20)     # l: [b,n,g,q,1]
        return (out1.transpose(0, 3, 1, 2, 4)
                .reshape(b, s, h, vd).astype(q.dtype))

    qr = q.reshape(b, nq, qc, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kr = k.reshape(b, nk, kc, kvh, hd).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(b, nk, kc, kvh, vd).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(nq, qc)
    kp = kv_positions.reshape(nk, kc)

    def q_block(qblk, qpos):
        # qblk [B, qc, KVH, G, hd]; qpos [qc]
        m0 = jnp.full((b, kvh, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, qc, vd), jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            kblk, vblk, kpos = inp  # [B,kc,KVH,hd], [B,kc,KVH,vd], [kc]
            s_ = jnp.einsum(
                "bqngd,bknd->bngqk", qblk, kblk,
                preferred_element_type=jnp.float32,
            ) * scale
            s_ = softcap(s_, cap)
            valid = kpos[None, :] >= 0
            if causal:
                valid = valid & (kpos[None, :] <= qpos[:, None])
            if window > 0:
                valid = valid & (kpos[None, :] > qpos[:, None] - window)
            s_ = jnp.where(valid[None, None, None], s_, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
            p = jnp.exp(s_ - m_new[..., None])
            p = jnp.where(valid[None, None, None], p, 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bngqk,bknd->bngqd", p, vblk,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kr, vr, kp))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        # [B,KVH,G,qc,vd] -> [B,qc,KVH*G,vd]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, qc, h, vd)

    if checkpoint:
        q_block = jax.checkpoint(q_block)

    out = jax.lax.map(lambda args: q_block(*args), (qr, qp))
    # [nq, B, qc, H, vd] -> [B, S, H, vd]
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, vd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Self-attention layer (train/prefill + decode)
# ---------------------------------------------------------------------------


def _project_q(p, x, cfg: ModelConfig, positions, theta):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
    return rope(q, positions[None, :], theta)


def _project_kv(p, x, cfg: ModelConfig, positions, theta):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "k_norm" in p:
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    k = rope(k, positions[None, :], theta)
    return k, v


def _project_mla(p, x, cfg: ModelConfig, positions, theta):
    """q and k of qk_nope + qk_rope dims per head, v of v_head_dim. The
    rope part of k is one head broadcast to every head. ``rope`` rotates
    the two halves of the rope dims, where DeepSeek-V3's code rotates
    interleaved pairs: a fixed permutation of the rope columns of ``wq``
    and ``wkv_a``."""
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions[None, :], theta)], -1)
    kv_a = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"].astype(x.dtype))
    latent = rms_norm(kv_a[..., :r], p["kv_norm"], cfg.rms_eps)
    k_rope = rope(kv_a[..., None, r:], positions[None, :], theta)
    kv = jnp.einsum("bsr,rhk->bshk", latent, p["wkv_b"].astype(x.dtype))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1]
                                  + (k_rope.shape[-1],))], -1)
    return q, k, v


def _project(p, x, cfg: ModelConfig, spec: LayerSpec, positions):
    theta = spec.rope_theta or cfg.rope_theta
    if spec.mixer == "mla":
        return _project_mla(p, x, cfg, positions, theta)
    q = _project_q(p, x, cfg, positions, theta)
    k, v = _project_kv(p, x, cfg, positions, theta)
    return q, k, v


def self_attention(
    p: Dict,
    x: jnp.ndarray,                 # [B, S, D]
    cfg: ModelConfig,
    spec: LayerSpec,
    *,
    positions: jnp.ndarray,         # [S]
    checkpoint: bool = False,
    causal: bool = True,
) -> jnp.ndarray:
    q, k, v = _project(p, x, cfg, spec, positions)
    out = chunked_attention(
        q, k, v,
        q_positions=positions,
        kv_positions=positions,
        causal=causal,
        window=spec.window,
        cap=cfg.logit_softcap,
        q_chunk=cfg.attn_q_chunk,
        kv_chunk=cfg.attn_kv_chunk,
        checkpoint=checkpoint,
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def init_kv_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                  max_len: int, abstract: bool = False) -> Dict:
    size = min(spec.window, max_len) if spec.window else max_len
    if spec.mixer == "mla":     # the expanded per-head k and v
        shape_k = (batch, size, cfg.num_heads,
                   cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        shape_v = (batch, size, cfg.num_heads, cfg.v_head_dim)
    else:
        shape_k = shape_v = (batch, size, cfg.num_kv_heads, cfg.head_dim)
    if abstract:
        return {
            "k": jax.ShapeDtypeStruct(shape_k, cfg.dtype),
            "v": jax.ShapeDtypeStruct(shape_v, cfg.dtype),
            "pos": jax.ShapeDtypeStruct((size,), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape_k, cfg.dtype),
        "v": jnp.zeros(shape_v, cfg.dtype),
        "pos": jnp.full((size,), -1, jnp.int32),
    }


def prefill_attention(
    p: Dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    spec: LayerSpec,
    cache: Dict,
    *,
    positions: jnp.ndarray,
) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence attention that also populates the KV cache."""
    q, k, v = _project(p, x, cfg, spec, positions)
    out = chunked_attention(
        q, k, v,
        q_positions=positions,
        kv_positions=positions,
        causal=True,
        window=spec.window,
        cap=cfg.logit_softcap,
        q_chunk=cfg.attn_q_chunk,
        kv_chunk=cfg.attn_kv_chunk,
    )
    size = cache["k"].shape[1]
    s = k.shape[1]
    if size >= s:
        new_cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
            "pos": jax.lax.dynamic_update_slice(cache["pos"], positions, (0,)),
        }
    else:
        # sliding-window ring buffer: slot(p) = p % size, matching decode.
        shift = (s - size) % size
        new_cache = {
            "k": jnp.roll(k[:, s - size:], shift, axis=1),
            "v": jnp.roll(v[:, s - size:], shift, axis=1),
            "pos": jnp.roll(positions[s - size:], shift, axis=0),
        }
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return proj, new_cache


def decode_attention(
    p: Dict,
    x: jnp.ndarray,                 # [B, 1, D]
    cfg: ModelConfig,
    spec: LayerSpec,
    cache: Dict,
    *,
    position: jnp.ndarray,          # scalar int32 current position
) -> Tuple[jnp.ndarray, Dict]:
    pos_arr = position[None]
    q, k_new, v_new = _project(p, x, cfg, spec, pos_arr)
    size = cache["k"].shape[1]
    slot = position % size if spec.window else position
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0)),
        "pos": jax.lax.dynamic_update_slice(cache["pos"], pos_arr, (slot,)),
    }
    out = chunked_attention(
        q, cache["k"], cache["v"],
        q_positions=pos_arr,
        kv_positions=cache["pos"],
        causal=True,
        window=spec.window,
        cap=cfg.logit_softcap,
        q_chunk=1,
        kv_chunk=cache["k"].shape[1] if cfg.decode_unchunked
        else cfg.attn_kv_chunk,
    )
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return proj, cache


# ---------------------------------------------------------------------------
# Cross-attention (VLM image layers / enc-dec decoder)
# ---------------------------------------------------------------------------


def cross_attention(
    p: Dict,
    x: jnp.ndarray,                 # [B, S, D]
    memory: jnp.ndarray,            # [B, M, D]
    cfg: ModelConfig,
    *,
    checkpoint: bool = False,
) -> jnp.ndarray:
    """No RoPE on cross-attention (memory has its own geometry)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bmd,dhk->bmhk", memory, p["wk"].astype(memory.dtype))
    v = jnp.einsum("bmd,dhk->bmhk", memory, p["wv"].astype(memory.dtype))
    m = memory.shape[1]
    out = chunked_attention(
        q, k, v,
        q_positions=jnp.zeros((x.shape[1],), jnp.int32),
        kv_positions=jnp.zeros((m,), jnp.int32),
        causal=False,
        window=0,
        cap=cfg.logit_softcap,
        q_chunk=cfg.attn_q_chunk,
        kv_chunk=cfg.attn_kv_chunk,
        checkpoint=checkpoint,
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
