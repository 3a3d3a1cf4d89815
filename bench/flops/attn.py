"""Model FLOPs per token of one attention mixer (and its norm), forward
and backward: 6 per weight of the q, k, v and o projections, plus the
score and value products, 12 * heads * head_dim * seq (the PaLM count,
arXiv:2204.02311 appendix B, causal masking not discounted)."""


def flops_per_token(model, seq):
    d, h, kv, hd = (model["d_model"], model["num_heads"],
                    model["num_kv_heads"], model["head_dim"])
    weights = d * h * hd + 2 * d * kv * hd + h * hd * d
    return 6 * weights + 12 * h * hd * seq
