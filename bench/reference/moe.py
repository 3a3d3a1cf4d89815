"""Reference expert layer (DeepSeek-V3's MoE, pre-norm, residual) over the
experts a chip holds, in float32.

The router scores all ``router_experts`` experts (sigmoid, or softmax),
chooses ``experts_per_token`` by score plus the selection bias, and
weights each chosen expert by its raw score over the chosen scores' sum,
times ``routed_scaling``. Each of the first ``num_experts`` experts (the
ones held here) computes its SwiGLU densely over every token, and keeps
it where the token chose it (a 0/1 mask times the gate weight, applied
before the down projection, which sums over the experts): no sort and no
grouped matmul. Choices of experts held elsewhere add nothing.
``num_shared_experts`` shared experts, one SwiGLU of that many times the
expert width, are added for every token.

The balance loss over all the router's scores (a sigmoid router's
sequence-wise one, arXiv:2412.19437 eq. 17-20; a softmax router's Switch
Transformer loss, arXiv:2101.03961 eq. 4), times ``router_aux_coef``, is
added to the gradient and not to the loss's value: the layer's output
carries its gradient into the backward pass (``_plus_gradient_of``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def params(model):
    d, er, e = model["d_model"], model["router_experts"], model["num_experts"]
    f = model["expert_d_ff"]
    p = {
        "ln2": ((d,), "param", ("zeros", 0.0)),
        "ffn.router": ((d, er), "param", ("normal", d ** -0.5)),
        "ffn.router_bias": ((er,), "param", ("zeros", 0.0)),
        "ffn.w_gate": ((e, d, f), "param", ("normal", d ** -0.5)),
        "ffn.w_up": ((e, d, f), "param", ("normal", d ** -0.5)),
        "ffn.w_down": ((e, f, d), "param", ("normal", f ** -0.5)),
    }
    sf = model.get("num_shared_experts", 0) * f
    if sf:
        p.update({
            "ffn.shared.w_gate": ((d, sf), "param", ("normal", d ** -0.5)),
            "ffn.shared.w_up": ((d, sf), "param", ("normal", d ** -0.5)),
            "ffn.shared.w_down": ((sf, d), "param", ("normal", sf ** -0.5)),
        })
    return p


@jax.custom_vjp
def _plus_gradient_of(y, extra):
    """``y``; the backward pass also differentiates the scalar ``extra``,
    as if it were added to the loss."""
    return y


def _pgo_fwd(y, extra):
    return y, None


def _pgo_bwd(_, g):
    return g, jnp.ones((), jnp.float32)


_plus_gradient_of.defvjp(_pgo_fwd, _pgo_bwd)


def _swiglu(x, w_gate, w_up, w_down, ops):
    gate = jax.nn.silu(ops.mm("bsd,df->bsf", x, w_gate))
    return ops.mm("bsf,fd->bsd", gate * ops.mm("bsd,df->bsf", x, w_up),
                  w_down)


def route(p, x, model, ops):
    """(gate weight of every router expert, 0 where not chosen, [B, S, E];
    the balance loss)."""
    er, k = model["router_experts"], model["experts_per_token"]
    s = x.shape[1]
    logits = ops.mm("bsd,de->bse", x, p["ffn.router"])
    sigmoid = model.get("router_scoring", "softmax") == "sigmoid"
    scores = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(scores + p["ffn.router_bias"], k)
    chosen = jnp.sum(jax.nn.one_hot(idx, er), axis=2)           # [B, S, E]
    picked = chosen * scores
    gate = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * model.get("routed_scaling", 1.0)
    if not sigmoid:
        first = jax.nn.one_hot(idx[..., 0], er)
        return gate, er * jnp.sum(jnp.mean(first, axis=(0, 1))
                                  * jnp.mean(scores, axis=(0, 1)))
    f_i = jnp.sum(chosen, axis=1) * (er / (k * s))
    p_i = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    return gate, jnp.mean(jnp.sum(f_i * p_i, axis=-1))


def apply(p, h, pos, model, ops):
    x = ops.rms(h, p["ln2"])
    gate, balance = route(p, x, model, ops)
    held = model["num_experts"]
    gate_h = jax.nn.silu(ops.mm("bsd,edf->ebsf", x, p["ffn.w_gate"]))
    up = ops.mm("bsd,edf->ebsf", x, p["ffn.w_up"])
    weighted = gate_h * up * jnp.moveaxis(gate[..., :held], -1, 0)[..., None]
    out = ops.mm("ebsf,efd->bsd", weighted, p["ffn.w_down"])
    if "ffn.shared.w_gate" in p:
        out = out + _swiglu(x, p["ffn.shared.w_gate"], p["ffn.shared.w_up"],
                            p["ffn.shared.w_down"], ops)
    return _plus_gradient_of(h + out, model["router_aux_coef"] * balance)
