"""Reference SwiGLU feed-forward block (pre-norm, residual), float32."""
from __future__ import annotations

import jax


def params(model):
    d, f = model["d_model"], model["d_ff"]
    return {
        "ln2": ((d,), "param", ("zeros", 0.0)),
        "ffn.w_gate": ((d, f), "param", ("normal", d ** -0.5)),
        "ffn.w_up": ((d, f), "param", ("normal", d ** -0.5)),
        "ffn.w_down": ((f, d), "param", ("normal", f ** -0.5)),
    }


def apply(p, h, pos, model, ops):
    x = ops.rms(h, p["ln2"])
    gate = jax.nn.silu(ops.mm("bsd,df->bsf", x, p["ffn.w_gate"]))
    up = ops.mm("bsd,df->bsf", x, p["ffn.w_up"])
    return h + ops.mm("bsf,fd->bsd", gate * up, p["ffn.w_down"])
