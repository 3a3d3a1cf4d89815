"""Device seconds a round in latent attention (``models/attention.py``,
the ``mla`` mixer, scope ``model.mla``): projections, kv norm, rope, the
chunked attention and the output projection of every layer, forward,
recomputation and backward, over every local step."""
from bench.scopes import per_round


def read(summary):
    return per_round(summary, "model.mla")
