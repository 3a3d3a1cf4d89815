"""Model FLOPs per token of one expert layer over the experts a chip
holds, forward and backward: 6 per weight of the router (all its experts),
of the shared experts, and of the SwiGLU of each held expert a token
reaches, ``experts_per_token * num_experts / router_experts`` of them on
average."""


def flops_per_token(model, seq):
    d, f = model["d_model"], model["expert_d_ff"]
    swiglu = 3 * d * f
    held = (model["experts_per_token"] * model["num_experts"]
            / model["router_experts"])
    return 6 * (d * model["router_experts"]
                + model.get("num_shared_experts", 0) * swiglu
                + held * swiglu)
