"""The mesh constructor, ``shard_map`` helpers, and the node-axis
substrate shared by every engine.

Targets JAX 0.9. Two facts of that release are absorbed here so the rest
of the codebase never touches them:

  * ``jax.make_mesh`` defaults to *Explicit* axis types, under which
    ``with_sharding_constraint`` on a batched value and vmaps over mixed
    specs are refused. ``make_mesh`` below asks for ``AxisType.Auto``
    (GSPMD-partitioned) axes, which is what every engine here assumes.
  * ``jax.shard_map`` takes the manual axes as ``axis_names=``; the
    ``shard_map`` wrapper below spells that as ``manual_axes``.

The second half of the module is the *node substrate*: one small object
that abstracts "the node axis" so the DFL algorithm (local-update scan,
CHOCO-G step, RNG folding, metrics) is written exactly once in
``repro.core.dfl`` and executed by two engines:

  * ``DenseSubstrate``   — nodes stacked on a leading [N, ...] array axis;
                           node ops are vmap / einsum-with-C / mean(axis=0).
                           Works for ANY doubly stochastic C.
  * ``ShardedSubstrate`` — nodes enumerated by manual mesh axes inside
                           ``shard_map``; node ops are identity / ppermute /
                           pmean. Requires a circulant (shift-structured) C
                           and moves only deg neighbor copies per gossip
                           step instead of the dense all-gather's N-1.

Both substrates fold PRNG keys identically (per-node key =
``fold_in(step_key, node_index)``), which is what makes dense-vs-sparse
parity exact even for stochastic losses and compressors.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any
AxisName = Union[str, Tuple[str, ...]]

__all__ = [
    "make_mesh",
    "axis_size",
    "mesh_axis_size",
    "shard_map",
    "supports_partial_auto",
    "NodeSubstrate",
    "DenseSubstrate",
    "BatchedSubstrate",
    "ShardedSubstrate",
]


# ---------------------------------------------------------------------------
# Meshes and shard_map
# ---------------------------------------------------------------------------


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` — THE one mesh
    constructor of this codebase. ``devices`` defaults to
    ``jax.devices()`` (a described topology's devices work too)."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names),
                         devices=devices)


def axis_size(axis_name: AxisName) -> int:
    """Size of a named mesh axis (or product over a tuple of axes), valid
    inside shard_map."""
    if isinstance(axis_name, (tuple, list)):
        return int(np.prod([axis_size(a) for a in axis_name]))
    return int(jax.lax.axis_size(axis_name))


def mesh_axis_size(mesh, axes: Optional[AxisName] = None) -> int:
    """Device count of ``axes`` on ``mesh`` (all axes when None) — THE one
    spelling of "how many nodes do these mesh axes enumerate". Works on
    ``jax.sharding.Mesh`` and ``AbstractMesh`` alike (both expose
    ``.shape``); an unknown axis raises ``KeyError``. Callers outside
    ``shard_map`` must use this, not ``axis_size`` (which needs a bound
    axis context) and not ad-hoc ``np.prod(mesh.shape[...])`` spellings
    (which drifted into four copies once)."""
    if axes is None:
        axes = tuple(mesh.axis_names)
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def shard_map(
    f: Callable,
    mesh,
    in_specs,
    out_specs,
    *,
    manual_axes: Optional[Sequence[str]] = None,
    check: bool = False,
):
    """``jax.shard_map``. ``manual_axes``: mesh axes the body is manual
    over (all axes when None); the rest stay auto (GSPMD-partitioned)."""
    kwargs = {}
    if manual_axes is not None and set(manual_axes) != set(mesh.axis_names):
        kwargs["axis_names"] = set(manual_axes)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check, **kwargs)


def supports_partial_auto() -> bool:
    """Whether shard_map with a non-trivial auto (GSPMD) axis set is usable
    — always, on the JAX this codebase targets. Engine auto-selection
    consults it (``core.dfl.sparse_engine_eligible``)."""
    return True


# ---------------------------------------------------------------------------
# Node substrates
# ---------------------------------------------------------------------------


class NodeSubstrate:
    """Abstracts the DFL node axis for the shared algorithm in core.dfl.

    Contract (N = number of nodes):
      * ``vmap(fn)``            — lift a per-node fn over the node axis.
      * ``node_keys(key)``      — per-node PRNG keys, fold_in(key, node_idx).
      * ``mix(tree, edge_mask=None)`` — one uncompressed gossip step
                                  X <- X C; ``edge_mask`` (traced [E] 0/1
                                  over ``topology.edges()``) drops masked
                                  edges and renormalizes onto the diagonal
                                  (bitwise the plain step at all ones).
      * ``mean_over_nodes(x)``  — mean over the node axis of per-node
                                  scalars (dense: leading array axis;
                                  sparse: pmean collective).
      * ``sum_per_node(x)``     — sum an array down to one scalar per node.
      * ``mean_tree(tree)``     — per-leaf f32 mean over nodes.

    Participation hooks (sporadic rounds; see docs/ARCHITECTURE.md):
      * ``node_mask_local(node_mask)``  — project the round's replicated
        [N] node mask to this substrate's local view (dense: the [N]
        vector itself; sparse: this node's scalar entry).
      * ``select_nodes(mask, new, old)`` — per-node select between two
        same-shaped trees (masked nodes keep ``old``); a bitwise identity
        for ``new`` wherever the mask is one.
      * ``masked_mean_over_nodes(x, mask)`` — mean of per-node scalars
        over ACTIVE nodes only; bitwise ``mean_over_nodes`` at all ones.
    """

    num_nodes: int

    def vmap(self, fn: Callable) -> Callable:
        raise NotImplementedError

    def node_keys(self, key: jax.Array):
        raise NotImplementedError

    def mix(self, tree: PyTree,
            edge_mask: Optional[jnp.ndarray] = None) -> PyTree:
        raise NotImplementedError

    def mean_over_nodes(self, x: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def sum_per_node(self, x: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def mean_tree(self, tree: PyTree) -> PyTree:
        raise NotImplementedError

    def node_mask_local(self, node_mask: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def select_nodes(self, mask_local: jnp.ndarray, new: PyTree,
                     old: PyTree) -> PyTree:
        raise NotImplementedError

    def masked_mean_over_nodes(self, x: jnp.ndarray,
                               mask_local: jnp.ndarray) -> jnp.ndarray:
        """mean(x * m) / max(mean(m), 1/N): exact ``/ 1.0`` at all ones,
        and 0 (not NaN) when every node is masked."""
        m = mask_local.astype(jnp.float32)
        num = self.mean_over_nodes(x * m)
        den = jnp.maximum(self.mean_over_nodes(m),
                          jnp.float32(1.0 / max(self.num_nodes, 1)))
        return num / den

    # -- shared derived ops (identical formulas on both engines) ----------

    def choco_move(self, x: PyTree, y: PyTree, mixed_y: PyTree,
                   gamma: float) -> Tuple[PyTree, PyTree]:
        """Fused CHOCO-G move (Alg. 2 l.6): x += gamma (C y - y); returns
        (x_new, x_new - y)."""

        def move(a, my, yy):
            return (a.astype(jnp.float32)
                    + gamma * (my.astype(jnp.float32) - yy.astype(jnp.float32))
                    ).astype(a.dtype)

        x_new = jax.tree_util.tree_map(move, x, mixed_y, y)
        diff = jax.tree_util.tree_map(lambda a, b: a - b, x_new, y)
        return x_new, diff

    def compress(self, comp, tree: PyTree, key: jax.Array) -> PyTree:
        """Apply the compressor Q leaf-wise (one node's tree + key)."""
        from repro.core.compression import compress_tree

        return compress_tree(comp, tree, key)

    def choco_step(self, comp, x: PyTree, y: PyTree, mixed_y: PyTree,
                   gamma: float, keys) -> Tuple[PyTree, PyTree]:
        """One full CHOCO-G inner iteration AFTER the mix (Alg. 2
        l.6-7,11): consensus move, compress the gap, update the shared
        estimates. Returns (x_new, y_new). The default is the unfused
        composition both engines executed historically (bit-identical);
        ``ShardedSubstrate`` overrides it with the single-pass fused
        kernel when ``use_kernels`` and Q is QSGD/TopK."""
        x_new, diff = self.choco_move(x, y, mixed_y, gamma)
        q = self.vmap(lambda d, k: self.compress(comp, d, k))(diff, keys)
        y_new = jax.tree_util.tree_map(lambda b, qq: b + qq, y, q)
        return x_new, y_new

    def consensus_sq(self, params: PyTree) -> jnp.ndarray:
        """||X (I - J)||_F^2 / N (Lemma 1's drift), via per-node deviation
        from the node mean."""
        mean = self.mean_tree(params)
        dev = None
        for leaf, m in zip(jax.tree_util.tree_leaves(params),
                           jax.tree_util.tree_leaves(mean)):
            d = (leaf.astype(jnp.float32) - m.astype(jnp.float32)) ** 2
            per_node = self.sum_per_node(d)
            dev = per_node if dev is None else dev + per_node
        return self.mean_over_nodes(dev)


class DenseSubstrate(NodeSubstrate):
    """Stacked-array node axis: every leaf [N, ...]; any topology."""

    def __init__(self, topology):
        self.topology = topology
        self.num_nodes = topology.num_nodes

    def vmap(self, fn):
        return jax.vmap(fn)

    def node_keys(self, key):
        return jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(self.num_nodes, dtype=jnp.int32))

    def mix(self, tree, edge_mask=None):
        from repro.core import mixing as mixing_lib

        return mixing_lib.mix_dense(tree, self.topology,
                                    edge_mask=edge_mask)

    def mean_over_nodes(self, x):
        return jnp.mean(x, axis=0)

    def sum_per_node(self, x):
        return jnp.sum(x, axis=tuple(range(1, x.ndim)))

    def mean_tree(self, tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.mean(x.astype(jnp.float32), axis=0), tree)

    def node_mask_local(self, node_mask):
        return node_mask

    def select_nodes(self, mask_local, new, old):
        def sel(nw, od):
            m = mask_local.astype(bool).reshape(
                (self.num_nodes,) + (1,) * (nw.ndim - 1))
            return jnp.where(m, nw, od)

        return jax.tree_util.tree_map(sel, new, old)


class BatchedSubstrate(DenseSubstrate):
    """Dense substrate over a SAMPLED cohort drawn from a virtual
    population (the node-batched mega-scale engine).

    The population is DATA, not hardware: training state stays stacked
    ``[population, ...]`` on the host device, and each round gathers the
    ``cohort_ids`` rows (a traced ``[C]`` int32 vector of GLOBAL node
    ids), runs the ordinary dense round over the C-node cohort
    ``topology``, and scatters the results back — non-cohort nodes are
    bitwise frozen. Per-round compute, gossip, and host data are all
    C-sized, so one machine simulates 10k-1M lightweight virtual nodes
    (the DFedAvg client-sampling regime, arXiv:2104.11375).

    Every node op is inherited from ``DenseSubstrate`` EXCEPT
    ``node_keys``, which folds the GLOBAL virtual-node id of each cohort
    slot instead of the slot index: a virtual node's per-step RNG stream
    is a function of its population identity, not of where a draw seated
    it. Two consequences, both load-bearing for the parity harness
    (tests/test_batched_parity.py):

      * at full population (``cohort_ids == arange(C)``, C == population)
        the gathers/scatters are identities and the folded ids equal the
        dense engine's slot indices, so a batched round is BITWISE the
        dense round — plain and CHOCO, masked and unmasked;
      * under a real C-of-V draw, a node's local-gradient/compressor
        noise is reproducible across different cohorts containing it.

    ``cohort_ids`` may be traced (the executor scans them as schedule
    xs — one executable across cohort draws, audited by
    ``cohort-recompile``) or ``None`` for the identity cohort.
    """

    def __init__(self, topology, population: int, cohort_ids=None):
        super().__init__(topology)
        population = int(population)
        if population < topology.num_nodes:
            raise ValueError(
                f"population {population} smaller than the cohort "
                f"topology's {topology.num_nodes} nodes")
        self.population = population
        self.cohort_ids = cohort_ids

    def _ids(self):
        if self.cohort_ids is None:
            return jnp.arange(self.num_nodes, dtype=jnp.int32)
        return jnp.asarray(self.cohort_ids, jnp.int32)

    def node_keys(self, key):
        return jax.vmap(lambda i: jax.random.fold_in(key, i))(self._ids())

    # -- population <-> cohort movement ------------------------------------

    def gather_cohort(self, tree: PyTree) -> PyTree:
        """Cohort rows of a ``[population, ...]``-stacked tree (identity
        when ``cohort_ids`` is None and C == population)."""
        if self.cohort_ids is None and self.num_nodes == self.population:
            return tree
        ids = self._ids()
        return jax.tree_util.tree_map(
            lambda x: jnp.take(x, ids, axis=0), tree)

    def scatter_cohort(self, full: PyTree, cohort: PyTree) -> PyTree:
        """Write cohort rows back into the population-stacked tree;
        non-cohort rows are untouched (bitwise)."""
        if self.cohort_ids is None and self.num_nodes == self.population:
            return cohort
        ids = self._ids()
        return jax.tree_util.tree_map(
            lambda f, c: f.at[ids].set(c), full, cohort)


class ShardedSubstrate(NodeSubstrate):
    """shard_map-manual node axis: leaves are one node's local shard; the
    mesh axes in ``node_axes`` enumerate nodes. Requires a circulant C
    (``topology.is_shift_structured()``); gossip is one ppermute per shift.

    ``use_kernels`` routes the hot path through the Pallas kernels in
    ``repro.kernels.ops`` (dispatch per ``repro.kernels.registry``:
    Mosaic on TPU, interpret off-TPU, validated against kernels/ref.py
    oracles in tests/test_kernels.py): the gossip accumulate
    (``gossip_mix``), and for C-DFL the FUSED compress-and-move step
    (``choco_qsgd_move`` / ``choco_topk_move`` — one kernel pass emits
    (x_new, y_new) instead of the move -> compress -> add chain). Other
    compressors fall back to the unfused ``choco_move`` kernel plus the
    library compressor.
    """

    def __init__(self, topology, node_axes: Sequence[str],
                 use_kernels: bool = False):
        assert topology.is_shift_structured(), (
            f"{topology.name} is not circulant; the sharded engine needs a "
            "shift-structured C (use the dense engine otherwise)")
        self.topology = topology
        self.node_axes = tuple(node_axes)
        self.axis: AxisName = (self.node_axes if len(self.node_axes) > 1
                               else self.node_axes[0])
        self.shifts = topology.shifts()
        self.self_weight = (float(topology.self_weights[0])
                            if topology.num_nodes else 1.0)
        self.num_nodes = topology.num_nodes
        self.use_kernels = use_kernels
        # Per-shift edge lookup for participation masks: entry [k, i] is
        # the canonical ``topology.edges()`` index of the edge node i
        # receives over on shift k (from node (i - s_k) mod N). Both
        # endpoints of an undirected edge resolve to the same entry, so a
        # masked edge renormalizes symmetrically on both sides.
        if self.shifts and topology.num_edges:
            eix = topology.edge_index()
            n = self.num_nodes
            self.shift_edge_idx = np.asarray(
                [[eix[tuple(sorted(((i - s) % n, i)))] for i in range(n)]
                 for (s, _) in self.shifts], dtype=np.int32)
        else:
            self.shift_edge_idx = np.zeros((0, self.num_nodes), np.int32)

    def shift_masks(self, edge_mask: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
        """This node's traced 0/1 scalar per shift, gathered from the
        round's replicated [E] edge mask."""
        idx = self.node_index()
        table = jnp.asarray(self.shift_edge_idx)
        return tuple(edge_mask[table[k, idx]].astype(jnp.float32)
                     for k in range(len(self.shifts)))

    def node_index(self) -> jnp.ndarray:
        idx = jnp.zeros((), jnp.int32)
        for a in self.node_axes:
            idx = idx * axis_size(a) + jax.lax.axis_index(a)
        return idx

    def vmap(self, fn):
        return fn  # already per-node under shard_map

    def node_keys(self, key):
        return jax.random.fold_in(key, self.node_index())

    def mix(self, tree, edge_mask=None):
        from repro.core import mixing as mixing_lib

        masks = (self.shift_masks(edge_mask)
                 if edge_mask is not None else None)
        if not self.use_kernels:
            return mixing_lib.mix_ppermute_shifts(
                tree, self.shifts, self.self_weight, self.axis,
                shift_masks=masks)

        from repro.kernels import ops as kernel_ops

        n_total = axis_size(self.axis)
        if masks is None:
            weights = jnp.asarray(
                [self.self_weight] + [w for _, w in self.shifts],
                jnp.float32)
        else:
            w_self, w_shift = mixing_lib.masked_shift_weights(
                self.shifts, self.self_weight, masks)
            weights = jnp.stack([w_self] + list(w_shift))

        def mix_leaf(x):
            if not self.shifts:
                return (self.self_weight * x.astype(jnp.float32)).astype(x.dtype)
            moved = [
                jax.lax.ppermute(
                    x, self.axis,
                    perm=[(src, (src + int(s)) % n_total)
                          for src in range(n_total)])
                for (s, _) in self.shifts
            ]
            return kernel_ops.gossip_mix(x, jnp.stack(moved), weights)

        return jax.tree_util.tree_map(mix_leaf, tree)

    def choco_move(self, x, y, mixed_y, gamma):
        if not self.use_kernels:
            return super().choco_move(x, y, mixed_y, gamma)
        from repro.kernels import ops as kernel_ops

        flat_x, treedef = jax.tree_util.tree_flatten(x)
        flat_y = jax.tree_util.tree_leaves(y)
        flat_my = jax.tree_util.tree_leaves(mixed_y)
        moved = [kernel_ops.choco_move(a, b, m, gamma)
                 for a, b, m in zip(flat_x, flat_y, flat_my)]
        x_new = jax.tree_util.tree_unflatten(treedef, [m[0] for m in moved])
        diff = jax.tree_util.tree_unflatten(treedef, [m[1] for m in moved])
        return x_new, diff

    def compress(self, comp, tree, key):
        from repro.core.compression import QSGD, TopK

        if not (self.use_kernels and isinstance(comp, (QSGD, TopK))):
            return super().compress(comp, tree, key)
        from repro.kernels import ops as kernel_ops

        # Same per-leaf key split and uniform noise as compression.QSGD, so
        # the kernel output is bit-identical to the library compressor
        # (tests/test_kernels.py::test_qsgd_kernel_agrees_with_library_compressor);
        # the TopK kernel path is bitwise by construction (same threshold,
        # same inclusive tie mask — see repro.kernels.topk).
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, max(len(leaves), 1))
        if isinstance(comp, TopK):
            out = [kernel_ops.top_k_compress(leaf, comp._k(leaf.size))
                   for leaf in leaves]
        else:
            out = [
                kernel_ops.qsgd_quantize(
                    leaf, jax.random.uniform(k, leaf.shape),
                    levels=comp.levels)
                for leaf, k in zip(leaves, keys)
            ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def choco_step(self, comp, x, y, mixed_y, gamma, keys):
        """Fused CHOCO compress-and-move: one kernel pass per leaf emits
        (x_new, y_new) directly (``repro.kernels.choco_fused``) instead
        of the move -> compress -> add chain with its three separate
        padded buffer round-trips. Engaged for QSGD and TopK under
        ``use_kernels``; other compressors keep the unfused composition.
        RNG discipline matches ``compression.compress_tree`` exactly: one
        fold_in'ed key per leaf, uniform noise drawn per QSGD leaf (TopK
        draws nothing). Numerics vs the unfused chain (f32, under jit):
        x_new bitwise for both compressors; y_new bitwise for TopK (the
        mask reads the same materialized gap its threshold was selected
        from) and within 1 f32 ulp for QSGD (the reconstruction multiply
        chain may round differently across separately-compiled kernels —
        the quantization level picked is identical). See
        tests/test_kernels.py and docs/ARCHITECTURE.md."""
        from repro.core.compression import QSGD, TopK

        if not (self.use_kernels and isinstance(comp, (QSGD, TopK))):
            return super().choco_step(comp, x, y, mixed_y, gamma, keys)
        from repro.kernels import ops as kernel_ops

        leaves_x, treedef = jax.tree_util.tree_flatten(x)
        leaves_y = jax.tree_util.tree_leaves(y)
        leaves_my = jax.tree_util.tree_leaves(mixed_y)
        leaf_keys = jax.random.split(keys, max(len(leaves_x), 1))
        moved = []
        for lx, ly, lmy, k in zip(leaves_x, leaves_y, leaves_my, leaf_keys):
            if isinstance(comp, TopK):
                moved.append(kernel_ops.choco_topk_move(
                    lx, ly, lmy, gamma, comp._k(lx.size)))
            else:
                noise = jax.random.uniform(k, lx.shape)
                moved.append(kernel_ops.choco_qsgd_move(
                    lx, ly, lmy, gamma, noise, levels=comp.levels))
        x_new = jax.tree_util.tree_unflatten(treedef, [m[0] for m in moved])
        y_new = jax.tree_util.tree_unflatten(treedef, [m[1] for m in moved])
        return x_new, y_new

    def mean_over_nodes(self, x):
        return jax.lax.pmean(x, self.axis)

    def sum_per_node(self, x):
        return jnp.sum(x)

    def mean_tree(self, tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x.astype(jnp.float32), self.axis), tree)

    def node_mask_local(self, node_mask):
        return node_mask[self.node_index()]

    def select_nodes(self, mask_local, new, old):
        keep = mask_local.astype(bool)
        return jax.tree_util.tree_map(
            lambda nw, od: jnp.where(keep, nw, od), new, old)
