"""Device seconds a round in the engine's local phase (engine,
``core/dfl.py:local_phase``, scope ``dfl.local``): the tau1 local steps of
every node, forward, backward and optimizer update."""
from bench.scopes import per_round


def read(summary):
    return per_round(summary, "dfl.local")
