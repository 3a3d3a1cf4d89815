"""Reference Mamba-1 selective-SSM mixer (pre-norm, residual), float32.

    x, z   = W_x n(h), W_z n(h)
    x_c    = silu(causal depthwise conv_K(x) + b)
    dt     = softplus(dt_proj(W_dt x_c) + dt_bias);  B, C = W_bc x_c
    h_t    = exp(dt_t A) h_{t-1} + dt_t x_c,t B_t,   A = -exp(A_log)
    y_t    = C_t . h_t + D x_c,t
    out    = W_out (y * silu(z))

The recurrence runs one position at a time (``lax.scan``), in chunks that
are recomputed in the backward pass. Falcon Mamba's RMS normalisation of
B, C and dt is not in the program, and so not here (a departure that
``bench/configs/falcon-mamba-7b.json`` records).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 128


def params(model):
    d = model["d_model"]
    di = model["ssm_expand"] * d
    st, k = model["ssm_state"], model["ssm_conv"]
    r = model.get("dt_rank") or -(-d // 16)
    return {
        "ln1": ((d,), "param", ("zeros", 0.0)),
        "mixer.wx": ((d, di), "param", ("normal", d ** -0.5)),
        "mixer.wz": ((d, di), "param", ("normal", d ** -0.5)),
        "mixer.conv_w": ((k, di), "param", ("normal", 0.2)),
        "mixer.conv_b": ((di,), "param", ("zeros", 0.0)),
        "mixer.w_dt": ((di, r), "param", ("normal", di ** -0.5)),
        "mixer.w_bc": ((di, 2 * st), "param", ("normal", di ** -0.5)),
        "mixer.dt_proj": ((r, di), "param", ("normal", r ** -0.5)),
        "mixer.dt_bias": ((di,), "param", ("zeros", 0.0)),
        "mixer.a_log": ((di, st), "float32", ("log_arange", 0.0)),
        "mixer.d_skip": ((di,), "float32", ("ones", 0.0)),
        "mixer.out_proj": ((di, d), "param", ("normal", di ** -0.5)),
    }


def apply(p, h, pos, model, ops):
    b, s, _ = h.shape
    st, k = model["ssm_state"], model["ssm_conv"]
    x = ops.rms(h, p["ln1"])
    xin = ops.mm("bsd,de->bse", x, p["mixer.wx"])
    z = ops.mm("bsd,de->bse", x, p["mixer.wz"])
    w = p["mixer.conv_w"]
    padded = jnp.concatenate(
        [jnp.zeros((b, k - 1, xin.shape[-1]), xin.dtype), xin], axis=1)
    conv = sum(padded[:, i:i + s] * w[i] for i in range(k))
    xc = jax.nn.silu(conv + p["mixer.conv_b"])
    dt = jax.nn.softplus(
        ops.mm("bsr,re->bse", ops.mm("bse,er->bsr", xc, p["mixer.w_dt"]),
               p["mixer.dt_proj"]) + p["mixer.dt_bias"])
    bc = ops.mm("bse,en->bsn", xc, p["mixer.w_bc"])
    bmat, cmat = bc[..., :st], bc[..., st:]
    a = -jnp.exp(p["mixer.a_log"])                            # [di, st]
    q = min(CHUNK, s)
    while s % q:
        q -= 1

    def chunk(hstate, inp):
        dt_c, x_c, b_c, c_c = inp                  # [B, q, di], ..., [B, q, st]

        def step(hh, t):
            dtt, xt, bt, ct = t
            hh = jnp.exp(dtt[..., None] * a) * hh + (dtt * xt)[..., None] \
                * bt[:, None, :]
            return hh, jnp.sum(hh * ct[:, None, :], axis=-1)

        per_pos = tuple(v.swapaxes(0, 1) for v in (dt_c, x_c, b_c, c_c))
        hstate, y = jax.lax.scan(step, hstate, per_pos)
        return hstate, y.swapaxes(0, 1)

    def to_chunks(v):
        return v.reshape(b, s // q, q, v.shape[-1]).swapaxes(0, 1)

    h0 = jnp.zeros((b, xc.shape[-1], st), jnp.float32)
    _, y = jax.lax.scan(jax.checkpoint(chunk), h0,
                        tuple(to_chunks(v) for v in (dt, xc, bmat, cmat)))
    y = y.swapaxes(0, 1).reshape(b, s, -1) + p["mixer.d_skip"] * xc
    y = y * jax.nn.silu(z)
    return h + ops.mm("bse,ed->bsd", y, p["mixer.out_proj"])
