"""Reference causal self-attention with grouped KV heads, optional
per-head RMS norm of q and k (Qwen3), and rotary positions, in float32.

Query head h reads KV head h // (heads / kv_heads). Scores are computed
for one block of query rows at a time against every key, so that a
4096-token sequence fits; each block is recomputed in the backward pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def params(model):
    d, h, kv, hd = (model["d_model"], model["num_heads"],
                    model["num_kv_heads"], model["head_dim"])
    p = {
        "ln1": ((d,), "param", ("zeros", 0.0)),
        "mixer.wq": ((d, h, hd), "param", ("normal", d ** -0.5)),
        "mixer.wk": ((d, kv, hd), "param", ("normal", d ** -0.5)),
        "mixer.wv": ((d, kv, hd), "param", ("normal", d ** -0.5)),
        "mixer.wo": ((h, hd, d), "param", ("normal", (h * hd) ** -0.5)),
    }
    if model.get("qk_norm"):
        p["mixer.q_norm"] = ((hd,), "param", ("zeros", 0.0))
        p["mixer.k_norm"] = ((hd,), "param", ("zeros", 0.0))
    return p


def rope(x, pos, theta):
    """x [B, S, H, hd]: rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq               # [S, half]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def apply(p, h, pos, model, ops):
    b, s, _ = h.shape
    heads, kvh = model["num_heads"], model["num_kv_heads"]
    g = heads // kvh
    theta = float(model["rope_theta"])
    x = ops.rms(h, p["ln1"])
    q = ops.mm("bsd,dhk->bshk", x, p["mixer.wq"])
    k = ops.mm("bsd,dhk->bshk", x, p["mixer.wk"])
    v = ops.mm("bsd,dhk->bshk", x, p["mixer.wv"])
    if "mixer.q_norm" in p:
        q = ops.rms(q, p["mixer.q_norm"])
        k = ops.rms(k, p["mixer.k_norm"])
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    k = jnp.repeat(k, g, axis=2)                         # [B, S, H, hd]
    v = jnp.repeat(v, g, axis=2)
    scale = q.shape[-1] ** -0.5
    qb = min(Q_BLOCK, s)
    while s % qb:
        qb -= 1

    def rows(qblk, qpos, k, v):
        sc = ops.mm("bqhd,bkhd->bhqk", qblk, k) * scale
        mask = pos[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        prob = jax.nn.softmax(sc, axis=-1)
        return ops.mm("bhqk,bkhd->bqhd", prob, v)

    out = jnp.concatenate(
        [jax.checkpoint(rows)(q[:, i:i + qb], pos[i:i + qb], k, v)
         for i in range(0, s, qb)], axis=1)
    return h + ops.mm("bshk,hkd->bsd", out, p["mixer.wo"])
