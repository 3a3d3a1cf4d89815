"""Reference latent attention (DeepSeek-V3's MLA with no q compression,
pre-norm, residual) in float32.

Per token: q = x W_q gives, per head, qk_nope_head_dim dims without
position and qk_rope_head_dim with rope; [c, k_r] = x W_kva, c the
kv_lora_rank-dim compressed kv, RMS-normalised, and k_r one rope key
shared by every head; [k_nope, v] = c W_kvb per head. Scores over
qk_nope + qk_rope dims, scaled by their inverse square root; the heads'
values (v_head_dim each) go through W_o. Rope rotates the two halves of
the rope dims (``attn.rope``). Scores are computed for one block of query
rows at a time against every key (``lax.map`` over the blocks); each
block is recomputed in the backward pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.attn import Q_BLOCK, rope


def params(model):
    d, h, r = model["d_model"], model["num_heads"], model["kv_lora_rank"]
    nope, rd, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    return {
        "ln1": ((d,), "param", ("zeros", 0.0)),
        "mixer.wq": ((d, h, nope + rd), "param", ("normal", d ** -0.5)),
        "mixer.wkv_a": ((d, r + rd), "param", ("normal", d ** -0.5)),
        "mixer.kv_norm": ((r,), "param", ("zeros", 0.0)),
        "mixer.wkv_b": ((r, h, nope + vd), "param", ("normal", r ** -0.5)),
        "mixer.wo": ((h, vd, d), "param", ("normal", (h * vd) ** -0.5)),
    }


def apply(p, h, pos, model, ops):
    b, s, _ = h.shape
    heads, r = model["num_heads"], model["kv_lora_rank"]
    nope, rd = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    theta = float(model["rope_theta"])
    x = ops.rms(h, p["ln1"])
    q = ops.mm("bsd,dhk->bshk", x, p["mixer.wq"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], -1)
    kv_a = ops.mm("bsd,dk->bsk", x, p["mixer.wkv_a"])
    latent = ops.rms(kv_a[..., :r], p["mixer.kv_norm"])
    k_rope = rope(kv_a[:, :, None, r:], pos, theta)          # [B, S, 1, rd]
    kv = ops.mm("bsr,rhk->bshk", latent, p["mixer.wkv_b"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rd))], -1)
    v = kv[..., nope:]
    scale = (nope + rd) ** -0.5
    qb = min(Q_BLOCK, s)
    while s % qb:
        qb -= 1

    def rows(qblk, qpos, k, v):
        sc = ops.mm("bqhd,bkhd->bhqk", qblk, k) * scale
        mask = pos[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        prob = jax.nn.softmax(sc, axis=-1)
        return ops.mm("bhqk,bkhd->bqhd", prob, v)

    nb = s // qb
    blocks = (jnp.moveaxis(q.reshape(b, nb, qb, heads, nope + rd), 1, 0),
              pos.reshape(nb, qb))
    out = jax.lax.map(lambda blk: jax.checkpoint(rows)(*blk, k, v), blocks)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, heads, -1)
    return h + ops.mm("bshk,hkd->bsd", out, p["mixer.wo"])
