"""Reference plain SGD, w <- w - lr grad in float32; it keeps no state."""
from __future__ import annotations


def init(w):
    return {}


def update(w, g, state, traffic):
    lr = float(traffic["lr"])
    return {k: w[k] - lr * g[k] for k in w}, state
