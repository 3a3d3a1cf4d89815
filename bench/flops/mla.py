"""Model FLOPs per token of one latent-attention mixer (MLA), forward and
backward: 6 per weight of the q, compressed-kv, kv up- and output
projections, plus the score and value products, 6 * heads * seq *
(q/k head dims + v head dims) (the PaLM count with the two head sizes of
MLA, causal masking not discounted)."""


def flops_per_token(model, seq):
    d, h, r = model["d_model"], model["num_heads"], model["kv_lora_rank"]
    nope, rd, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    weights = (d * h * (nope + rd) + d * (r + rd) + r * h * (nope + vd)
               + h * vd * d)
    return 6 * weights + 6 * h * seq * (nope + rd + vd)
