"""Model-averaging (gossip) primitives over a stacked node axis.

Parameters of N DFL nodes are carried as pytrees whose every leaf has a
leading node dimension of size N (the paper's X_t = [w^(1) ... w^(N)],
transposed to rows). One gossip step is X <- X @ C along that axis.

Two implementations:

* ``mix_dense``     — literal matrix form (einsum over the node axis).
                      Correct for ANY doubly stochastic C. Under pjit with
                      the node axis sharded, XLA lowers this to all-gather +
                      local contraction: the paper-faithful baseline.
* ``mix_ppermute``  — exploits sparsity: for a circulant (shift-structured)
                      C, one ``jax.lax.ppermute`` per shift inside
                      ``shard_map``, i.e. neighbor-only traffic on the ICI
                      ring. The beyond-paper optimized path.

Both agree to float tolerance (tested); the dry-run roofline records the
collective-byte difference.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import Topology
from repro.core.substrate import axis_size

PyTree = Any

__all__ = [
    "mix_dense",
    "mix_dense_power",
    "mix_ppermute_shifts",
    "masked_mixing_matrix",
    "masked_shift_weights",
    "gossip_copies_per_step",
    "mixing_bytes_per_step",
]


def _as_mixing_array(topology: Topology, dtype) -> jnp.ndarray:
    return jnp.asarray(topology.mixing, dtype=dtype)


def _edge_tables(topology: Topology) -> Tuple[np.ndarray, np.ndarray]:
    """(has_edge [N,N] bool, eidx [N,N] int32) for the canonical edge list."""
    n = topology.num_nodes
    has_edge = np.zeros((n, n), dtype=bool)
    eidx = np.zeros((n, n), dtype=np.int32)
    for e, (a, b) in enumerate(topology.edges()):
        has_edge[a, b] = has_edge[b, a] = True
        eidx[a, b] = eidx[b, a] = e
    return has_edge, eidx


def masked_mixing_matrix(
    topology: Topology, edge_mask: jnp.ndarray, dtype
) -> jnp.ndarray:
    """The runtime confusion matrix for a round with masked edges.

    ``edge_mask`` is a traced [E] 0/1 vector over ``topology.edges()``. A
    masked edge carries no gossip: its off-diagonal entries are zeroed and
    the lost weight moves onto BOTH endpoints' diagonals, so the result
    stays symmetric doubly stochastic. With all-ones masks the arithmetic
    is exact (multiply by 1.0, add 0.0) and the matrix is bitwise equal to
    ``topology.mixing`` — the participation path degrades to the plain
    round with no numerical drift.
    """
    cm = jnp.asarray(topology.mixing, dtype=dtype)
    if topology.num_edges == 0:
        return cm
    has_edge, eidx = _edge_tables(topology)
    gate = jnp.where(jnp.asarray(has_edge),
                     edge_mask.astype(dtype)[jnp.asarray(eidx)],
                     jnp.ones((), dtype))
    masked = cm * gate
    # removed[i] = sum_j C[j, i] (1 - gate[j, i]) — the weight node i no
    # longer receives, returned to its self loop.
    removed = jnp.sum(cm * (jnp.ones((), dtype) - gate), axis=0)
    return masked + jnp.diag(removed)


def mix_dense(
    params: PyTree, topology: Topology,
    edge_mask: Optional[jnp.ndarray] = None,
) -> PyTree:
    """One gossip step, X <- X C, as a dense contraction over the node axis.

    Every leaf: [N, ...] -> [N, ...] with out[i] = sum_j C[j, i] leaf[j].
    ``edge_mask`` (traced [E] over ``topology.edges()``) replaces C with
    ``masked_mixing_matrix`` — bitwise-identical at all ones.
    """
    c = topology.mixing

    def mix_leaf(x: jnp.ndarray) -> jnp.ndarray:
        # ellipsis einsum keeps the trailing-dim shardings intact (an
        # explicit reshape-to-2D here makes GSPMD all-gather whole stacked
        # weight trees — observed 200 GiB/device before this was fixed).
        dtype = jnp.promote_types(x.dtype, jnp.float32)
        if edge_mask is None:
            cm = _as_mixing_array(topology, dtype)
        else:
            cm = masked_mixing_matrix(topology, edge_mask, dtype)
        # HIGHEST: a TPU contracts f32 at bf16 precision by default, which
        # rounds weights such as 1/3 and leaves C no longer doubly
        # stochastic.
        mixed = jnp.einsum("ji,j...->i...", cm, x.astype(cm.dtype),
                           precision=jax.lax.Precision.HIGHEST)
        return mixed.astype(x.dtype)

    del c
    return jax.tree_util.tree_map(mix_leaf, params)


def mix_dense_power(params: PyTree, topology: Topology, tau2: int) -> PyTree:
    """tau2 gossip steps collapsed into one contraction with C^tau2.

    Mathematically identical to applying ``mix_dense`` tau2 times (for
    uncompressed DFL only — C-DFL must iterate because compression is
    interleaved). Saves (tau2-1) rounds of collectives: a legitimate
    beyond-paper optimization for plain DFL, recorded in §Perf.
    """
    # repro-lint: disable=no-host-coercion-of-device-scalars (tau2 is a static trace-time int here: dense_power bakes C^tau2 in, and make_round_fn rejects dynamic_taus for it)
    cpow = np.linalg.matrix_power(topology.mixing, int(tau2))
    topo_pow = Topology(
        name=f"{topology.name}^%d" % tau2,
        mixing=cpow,
        neighbors=topology.neighbors,  # unused by the dense path
        self_weights=np.diag(cpow).copy(),
    )
    return mix_dense(params, topo_pow)


def masked_shift_weights(
    shifts: Sequence[Tuple[int, float]],
    self_weight: float,
    shift_masks: Sequence[jnp.ndarray],
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """(effective self weight, per-shift effective weights) for one node.

    ``shift_masks[k]`` is this node's traced 0/1 scalar for shift k's edge.
    A masked shift contributes weight 0 and its weight returns to the self
    loop: ``w_self + sum_k w_k (1 - m_k)``. With all-ones masks each term
    is an exact ``+ 0.0`` / ``* 1.0`` so the weights are bitwise the static
    ones — the masked sparse gossip then matches the legacy path bitwise.
    """
    one = jnp.float32(1.0)
    w_self = jnp.float32(self_weight)
    for (_, w), m in zip(shifts, shift_masks):
        w_self = w_self + jnp.float32(w) * (one - m.astype(jnp.float32))
    eff = tuple(jnp.float32(w) * m.astype(jnp.float32)
                for (_, w), m in zip(shifts, shift_masks))
    return w_self, eff


def mix_ppermute_shifts(
    params: PyTree,
    shifts: Sequence[Tuple[int, float]],
    self_weight: float,
    axis_name: str | Tuple[str, ...],
    shift_masks: Optional[Sequence[jnp.ndarray]] = None,
) -> PyTree:
    """One gossip step for a circulant C, inside shard_map.

    Must be called from within a ``shard_map`` whose mesh axis ``axis_name``
    enumerates the nodes and over which every leaf is sharded to a single
    node per device slice (leading node dim of local size 1).

    shifts: [(s, w)] meaning node i receives weight w from node (i - s) mod N
    (equivalently sends to i + s). self_weight: diagonal of C. An empty
    shift list is the degenerate no-edge topology (C = I): no traffic, every
    node keeps self_weight (= 1) of itself.

    shift_masks: optional per-shift traced 0/1 scalars for THIS node (one
    per entry of ``shifts``, gathered from the round's edge mask by the
    substrate). The ppermutes still run on every shift — masking gates the
    accumulation weight, not the collective, so the compiled HLO (and the
    ``collective-matching`` audit) is identical across masks and the
    superstep never recompiles.
    """
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    n_total = axis_size(names)

    if shift_masks is not None:
        assert len(shift_masks) == len(shifts)
        w_self, w_shift = masked_shift_weights(shifts, self_weight,
                                               shift_masks)
    else:
        w_self, w_shift = self_weight, tuple(w for (_, w) in shifts)

    def perm_for(shift: int):
        return [(src, (src + shift) % n_total) for src in range(n_total)]

    def mix_leaf(x: jnp.ndarray) -> jnp.ndarray:
        acc = (w_self * x.astype(jnp.float32))
        for (s, _), w in zip(shifts, w_shift):
            moved = jax.lax.ppermute(x, names if len(names) > 1 else names[0],
                                     perm=perm_for(int(s)))
            acc = acc + w * moved.astype(jnp.float32)
        return acc.astype(x.dtype)

    return jax.tree_util.tree_map(mix_leaf, params)


def gossip_copies_per_step(topology: Topology, engine: str) -> int:
    """Model copies each node RECEIVES per gossip step — THE accounting
    helper; every wire-cost number in the repo derives from it.

    engine:
      "sparse" — per-neighbor traffic (the ppermute engine, and what a real
                 network deployment ships): max_degree copies.
      "dense"  — the dense einsum's all-gather lowering: N - 1 copies,
                 regardless of how sparse C itself is.
      "auto"   — whichever engine the launcher would select for this
                 topology (sparse iff shift-structured).
    """
    if engine == "auto":
        engine = "sparse" if topology.is_shift_structured() else "dense"
    if engine == "sparse":
        return topology.max_degree
    if engine == "dense":
        return max(topology.num_nodes - 1, 0)
    raise ValueError(f"unknown engine {engine!r}")


def mixing_bytes_per_step(
    topology: Topology, param_bytes: int, sparse: bool
) -> int:
    """Bytes on the wire per node per gossip step (analytic accounting).

    dense (all-gather lowering): every node receives the other N-1 models.
    sparse (ppermute): every node receives deg models.
    """
    engine = "sparse" if sparse else "dense"
    return gossip_copies_per_step(topology, engine) * param_bytes
