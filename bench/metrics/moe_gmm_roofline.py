"""Share of the chip's bf16 peak that the held experts' grouped matmuls
reach (expert layer, ``models/moe.py``, scope ``moe.experts``).

The operations are those of the rows the program counted (``moe_rows``
in the window's ``round_metrics``: held (token, expert) pairs, over
layers, local steps and nodes): a SwiGLU row is 6 * d_model *
expert_d_ff in each forward pass the compiled program runs (two, where
the layer is rematerialised in the backward pass, ``remat`` on as in
every configuration) and 12 * d_model * expert_d_ff in the backward
pass. The least time is those operations, per chip, over the peak
(``bench/peaks.json``); the share is that over the device seconds of
the grouped matmuls, all three passes: the ops under ``moe.experts`` and
the ``ragged-dot-*`` kernel calls the TPU compiler leaves unscoped."""
from bench.scopes import per_round


def read(summary):
    if per_round(summary, "moe.experts") is None:
        return None
    rows = summary.get("round_metrics", {}).get("moe_rows")
    if not rows:
        return None
    model = summary["model"]
    row = model["d_model"] * model["expert_d_ff"]
    passes = 2 if model.get("remat", True) else 1
    flops = rows * (6 * row * passes + 12 * row) / summary["chips"]
    least = flops / summary["peaks"]["bf16_flops"]
    seconds = summary["scopes"]["moe.experts"] + sum(
        v for k, v in summary.get("scope_ops", {}).items()
        if k.startswith("unscoped ragged-dot"))
    return 100.0 * least / seconds
