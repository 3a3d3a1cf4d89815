"""Token traffic for the DFL training cells, made on the host from the seed.

A copy of the order-2 Markov corpus of ``repro.data.lm.SyntheticLM`` (a
shared transition chain with ``branching`` successors per token, and one
perturbation chain per node mixed in with probability ``noniid``), sampled
for all rows at once. The program receives only the tokens.

A mix file (``bench/traffic/<mix>.json``) gives nodes, tau1, superstep,
batch, seq, noniid, branching and ``pool``: the number of distinct
supersteps of batches made before the window, which the window cycles
through. Every row of the pool is drawn anew, so the first supersteps,
which the correctness check follows, all see different tokens.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _chain(rng: np.random.Generator, vocab: int, branching: int):
    nxt = rng.integers(0, vocab, size=(vocab, branching))
    logits = rng.normal(size=(vocab, branching)).astype(np.float32)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    return nxt.astype(np.int32), np.cumsum(probs, axis=-1)


def sample_rows(rng: np.random.Generator, node_of_row: np.ndarray,
                vocab: int, nodes: int, length: int, noniid: float,
                branching: int) -> np.ndarray:
    """[rows, length] token streams; row i follows node
    ``node_of_row[i]``'s mixture of the shared and its own chain."""
    k = min(branching, vocab)
    chains = [_chain(rng, vocab, k) for _ in range(nodes + 1)]  # 0: shared
    nxt = np.stack([c[0] for c in chains])                 # [N+1, V, k]
    cum = np.stack([c[1] for c in chains])                 # [N+1, V, k]
    rows = node_of_row.shape[0]
    own = node_of_row.astype(np.int64) + 1
    use_node = rng.random((rows, length)) < noniid
    u = rng.random((rows, length)).astype(np.float32)
    cur = rng.integers(0, vocab, size=rows)
    out = np.empty((rows, length), np.int32)
    for i in range(length):
        tab = np.where(use_node[:, i], own, 0)
        j = (cum[tab, cur] < u[:, i, None]).sum(axis=1)
        cur = nxt[tab, cur, np.minimum(j, k - 1)]
        out[:, i] = cur
    return out


def make_pool(traffic: Dict, vocab: int, seed: int
              ) -> List[List[Dict[str, np.ndarray]]]:
    """``pool`` supersteps of ``superstep`` rounds; each round is a dict of
    ``tokens``/``labels`` int32 arrays shaped [tau1, nodes, batch, seq]."""
    p, k = int(traffic["pool"]), int(traffic["superstep"])
    t1, n = int(traffic["tau1"]), int(traffic["nodes"])
    b, s = int(traffic["batch"]), int(traffic["seq"])
    rng = np.random.default_rng(int(seed))
    shape = (p, k, t1, n, b)
    node_of_row = np.broadcast_to(
        np.arange(n).reshape(1, 1, 1, n, 1), shape).reshape(-1)
    rows = sample_rows(rng, node_of_row, vocab, n, s + 1,
                       float(traffic["noniid"]), int(traffic["branching"]))
    rows = rows.reshape(shape + (s + 1,))
    return [[{"tokens": np.ascontiguousarray(rows[i, r, ..., :-1]),
              "labels": np.ascontiguousarray(rows[i, r, ..., 1:])}
             for r in range(k)] for i in range(p)]


def tokens_per_superstep(traffic: Dict) -> int:
    return (int(traffic["superstep"]) * int(traffic["tau1"])
            * int(traffic["nodes"]) * int(traffic["batch"])
            * int(traffic["seq"]))
