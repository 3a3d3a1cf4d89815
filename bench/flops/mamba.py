"""Model FLOPs per token of one Mamba-1 mixer, forward and backward: 6 per
weight of the x, z, dt (low rank), B/C and output projections and of the
depthwise convolution, plus the selective scan, 18 per (channel, state)
element: about 6 forward (exp(dt A), dt x B, the recurrence's multiply and
add, C . h) and twice that backward. The scan is recomputed in the
backward pass; that recomputation is not counted."""


def flops_per_token(model, seq):
    d = model["d_model"]
    di = model["ssm_expand"] * d
    st, k = model["ssm_state"], model["ssm_conv"]
    r = model.get("dt_rank") or -(-d // 16)
    weights = 2 * d * di + di * r + r * di + di * 2 * st + di * d + k * di
    return 6 * weights + 18 * di * st
