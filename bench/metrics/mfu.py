"""Model FLOP/s utilization of the traced window (model step,
``models/transformer.py`` and ``models/mamba.py``): model FLOPs per token
(``bench/flops``; recomputation not counted) times the tokens trained in
the window, over the window's wall time and the chips' bf16 peak
(``bench/peaks.json``)."""


def read(summary):
    window = summary.get("wall_s")
    if not window or not summary.get("tokens"):
        return None
    achieved = summary["flops_per_token"] * summary["tokens"] / window
    return 100.0 * achieved / (summary["peaks"]["bf16_flops"]
                               * summary["chips"])
