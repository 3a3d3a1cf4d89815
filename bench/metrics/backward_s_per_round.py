"""Device seconds a round in the local steps' backward pass (engine,
``core/dfl.py:local_phase``): the ops under ``dfl.local`` whose
``op_name`` holds JAX's VJP marker ``transpose(``, remat recomputation
included."""
from bench.scopes import per_round


def read(summary):
    if per_round(summary, "dfl.local") is None:
        return None
    return summary["backward_s"] / summary["rounds"]
