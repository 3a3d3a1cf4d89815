"""Device seconds a round in the engine's gossip phase (engine,
``core/dfl.py:gossip_phase``, scope ``dfl.gossip``): the tau2 exchange
steps, plain or CHOCO, with the collective-permutes between chips on the
sparse engine."""
from bench.scopes import per_round


def read(summary):
    return per_round(summary, "dfl.gossip")
