"""Device seconds a round in the TopK threshold (compression,
``kernels/ops.py`` and ``core/compression.py``, scope
``compress.threshold``): the k-th largest magnitude of each leaf of each
node, once per gossip step. None in a cell that runs no TopK."""
from bench.scopes import per_round


def read(summary):
    return per_round(summary, "compress.threshold")
