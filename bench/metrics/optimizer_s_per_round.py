"""Device seconds a round in the local steps' optimizer update (engine,
``core/dfl.py``, scope ``dfl.local.update``: the update, ``p + u`` and
the sharding constraint)."""
from bench.scopes import per_round


def read(summary):
    return per_round(summary, "dfl.local.update")
