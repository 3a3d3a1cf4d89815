"""Mixture-of-Experts FFN: a dropless layer over the experts it holds.

The router scores all ``router_experts`` experts of the model (float32,
at ``Precision.HIGHEST``) and picks ``experts_per_token`` of them by score
plus a selection bias (DeepSeek-V3's ``noaux_tc``; the bias only chooses,
it never weights). Gate weights are the chosen raw scores, renormalised
over the chosen ones and scaled by ``routed_scaling``. This layer holds the
first ``num_experts`` of the router's experts (one chip's share of an
expert-parallel deployment, or all of them) and computes, for every
(token, choice) pair routed to a held expert, that expert's SwiGLU:

  route    the pairs are sorted by expert into a static buffer of
           tokens x experts_per_token rows (every choice held is the worst
           case, so nothing is ever dropped); pairs of experts held
           elsewhere sort past the held groups and are not computed;
  experts  SwiGLU over the buffer with grouped matmuls
           (``jax.lax.ragged_dot``, one group per held expert);
  combine  the rows go back to (token, choice) order, and each token adds
           its held choices' rows times their gate weights.

What the experts held elsewhere would add is left out, as the chips that
hold them would add it. ``num_shared_experts`` shared experts, one SwiGLU
of ``num_shared_experts * expert_width``, are added for every token.

The balance loss follows the router's scoring, over all of its scores. A
sigmoid router takes DeepSeek-V3's sequence-wise loss (arXiv:2412.19437
eq. 17-20): per sequence, sum_i f_i P_i with f_i = E / (k T) x (tokens
choosing i) and P_i the mean over tokens of the scores normalised to sum
1. A softmax router takes the Switch Transformer's load-balance loss
(arXiv:2101.03961 eq. 4): E sum_i f_i P_i over the whole batch, f_i the
share of tokens whose first choice is i and P_i the mean probability. The
update of the selection bias (aux-loss-free balancing) happens outside the
gradient and is not applied: the bias is a leaf with zero gradient.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, ParamFactory, swiglu


def moe_params(f: ParamFactory, cfg: ModelConfig) -> Dict:
    d, ff, e = cfg.d_model, cfg.expert_width, cfg.num_experts
    p = {
        "router": f.dense((d, cfg.router_width), ("embed", None), scale=0.02),
        "router_bias": f.zeros((cfg.router_width,), (None,)),
        "w_gate": f.dense((e, d, ff), ("experts", "embed", "mlp")),
        "w_up": f.dense((e, d, ff), ("experts", "embed", "mlp")),
        "w_down": f.dense((e, ff, d), ("experts", "mlp", "embed")),
    }
    if cfg.num_shared_experts:
        sf = cfg.num_shared_experts * ff
        p["shared"] = {
            "w_gate": f.dense((d, sf), ("embed", "mlp")),
            "w_up": f.dense((d, sf), ("embed", "mlp")),
            "w_down": f.dense((sf, d), ("mlp", "embed")),
        }
    return p


def route(p: Dict, x: jnp.ndarray, cfg: ModelConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x [B, S, D] -> (chosen experts [B, S, k], gate weights [B, S, k]
    float32, balance loss). Every score is the router's."""
    b, s, _ = x.shape
    e, k = cfg.router_width, cfg.experts_per_token
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32), k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling
    if cfg.router_scoring == "sigmoid":
        chosen = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=2)
        f_i = jnp.sum(chosen, axis=1) * (e / (k * s))            # [B, E]
        p_i = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True),
                       axis=1)
        aux = jnp.mean(jnp.sum(f_i * p_i, axis=-1))
    else:
        first = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32)
        aux = e * jnp.sum(jnp.mean(first, axis=(0, 1))
                          * jnp.mean(scores, axis=(0, 1)))
    return idx, gate, aux


def moe_ffn(
    p: Dict,
    x: jnp.ndarray,                 # [B, S, D]
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (out [B, S, D], balance loss, held (token, expert) pairs
    computed), the last two float32 scalars."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    xf = x.reshape(t, d)
    with jax.named_scope("moe.route"):
        idx, gate, aux = route(p, x, cfg)
        pair_expert = idx.reshape(t * k)
        held = pair_expert < e
        # held experts' pairs first, grouped by expert; the rest after
        key = jnp.where(held, pair_expert, e)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, e, dtype=jnp.int32), axis=0)
        # rows past the last group belong to no group: the grouped matmul
        # leaves them undefined in its results and its gradients (on the
        # TPU they are not zero), so they are zeroed on both sides
        grouped = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
        rows = jnp.where(grouped, xf[order // k], 0)             # [T*k, D]
    with jax.named_scope("moe.experts"):
        g = jax.lax.ragged_dot(rows, p["w_gate"].astype(x.dtype), sizes)
        u = jax.lax.ragged_dot(rows, p["w_up"].astype(x.dtype), sizes)
        h = jnp.where(grouped, jax.nn.silu(g.astype(jnp.float32)).astype(
            x.dtype) * u, 0)
        y = jax.lax.ragged_dot(h, p["w_down"].astype(x.dtype), sizes)
        y = jnp.where(grouped, y, 0)
    with jax.named_scope("moe.combine"):
        # back to (token, choice) order; pairs held elsewhere add nothing
        y = y[jnp.argsort(order)]
        out = jnp.sum(y.reshape(t, k, d).astype(jnp.float32)
                      * gate.reshape(t, k, 1), axis=1)
        out = out.astype(x.dtype).reshape(b, s, d)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            sp = p["shared"]
            out = out + swiglu(x, sp["w_gate"], sp["w_up"], sp["w_down"])
    return out, aux, jnp.sum(sizes).astype(jnp.float32)
