"""Model FLOPs per token, one file per layer kind (a mixer or ffn name of
a configuration's pattern), each with ``flops_per_token(model, seq)``.
``model_flops_per_token`` adds the output head."""
from __future__ import annotations

from typing import Any, Callable, Dict


def model_flops_per_token(model: Dict[str, Any], seq: int,
                          kind: Callable[[str], Any]) -> float:
    """Forward and backward FLOPs per trained token of the whole model:
    every layer of the pattern, times its periods, and the output head
    (6 * d_model * vocab; the embedding lookup is a gather). Recomputation
    is not counted. ``kind(name)`` loads ``bench/flops/<name>.py``."""
    periods = model["num_layers"] // len(model["pattern"])
    total = 6.0 * model["d_model"] * model["vocab_size"]
    for spec in model["pattern"]:
        for name in (spec.get("mixer", "attn"), spec.get("ffn", "mlp")):
            if name != "none":
                total += periods * kind(name).flops_per_token(model, seq)
    return total
