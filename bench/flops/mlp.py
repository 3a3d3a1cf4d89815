"""Model FLOPs per token of one SwiGLU feed-forward block, forward and
backward: 6 per weight of the gate, up and down projections."""


def flops_per_token(model, seq):
    return 6 * 3 * model["d_model"] * model["d_ff"]
