"""Device seconds a round in the expert layer (``models/moe.py``, scope
``model.moe``): routing (``moe.route``), the held experts' grouped
matmuls (``moe.experts``), the combine (``moe.combine``) and the shared
experts (``moe.shared``) of every layer, forward, recomputation and
backward, over every local step. The TPU compiler replaces each grouped
matmul by a kernel call named ``ragged-dot-*`` that keeps no ``op_name``,
so that time is in no scope: it is added from the unscoped ops."""
from bench.scopes import per_round


def read(summary):
    scoped = per_round(summary, "model.moe")
    if scoped is None:
        return None
    grouped = sum(v for k, v in summary.get("scope_ops", {}).items()
                  if k.startswith("unscoped ragged-dot"))
    return scoped + grouped / summary["rounds"]
