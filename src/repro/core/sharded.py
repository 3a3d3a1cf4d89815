"""Production sharded DFL round: node axis manual, model axes auto.

``make_sharded_round_fn`` builds the sparse engine behind
``core.dfl.make_round_fn(..., engine="sparse")``: each DFL node's local
updates run as ordinary (GSPMD-partitioned) JAX under a ``shard_map`` that
is manual ONLY over the node mesh axes; the gossip stage is per-neighbor
``collective-permute`` (ring traffic = deg copies instead of the dense
path's N-1-copy all-gather). Supports plain DFL and CHOCO-G C-DFL
(compression applied node-locally, neighbor estimates fetched by ppermute —
equivalent to Alg. 2's replicated w_hat bookkeeping), plus the Pallas
kernel hot path (``use_kernels=True``: kernel gossip accumulate and the
FUSED CHOCO compress-and-move for QSGD/TopK via
``ShardedSubstrate.choco_step`` — dispatch rules in
``repro.kernels.registry``, path diagram in docs/ARCHITECTURE.md).

This module owns ONLY the shard_map plumbing (specs, squeeze/unsqueeze of
the local node dim). The round itself — local-update scan, CHOCO step, RNG
folding, metrics — is ``core.dfl.round_body`` running on a
``ShardedSubstrate``, i.e. the exact same code the dense engine executes,
which is what keeps the engines from drifting apart again.

Engine selection rule (``core.dfl.resolve_engine`` when engine="auto";
``launch.steps.select_engine`` adds its auto-axis guard): sparse iff ``cfg.topology.is_shift_structured()`` (circulant
C: ring/torus rows of the mesh; includes the degenerate no-edge C = I),
no dense-only features (schedules, dense_power), and the node mesh axes
enumerate exactly the N > 1 nodes. The dense engine (``core.dfl``) remains
the general-topology path and the numerical reference
(tests/test_multidevice.py checks they agree bit-for-bit-ish, compressed
and uncompressed). JAX API details are absorbed by
``repro.core.substrate``.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import substrate as substrate_lib
from repro.core.dfl import (DFLConfig, DFLState, pipeline_drain_body,
                            pipeline_round_body, round_body)
from repro.core.substrate import ShardedSubstrate

PyTree = Any

# How far apart the two engines' per-round losses may be on the same seed.
# They add each gossip step's terms in a different order; with bf16
# parameters an f32 rounding difference now and then flips a parameter by
# one ulp, and the losses drift apart (with float32 parameters they are
# bitwise equal). Readings on the reduced qwen3-1.7b, 4-node ring,
# tau=(2, 2), 4 rounds, 8 init seeds, on CPU: at most 2.9e-4 at seq 32 and
# 1.04e-4 at seq 256.
ENGINE_PARITY_LOSS_ATOL = 5e-4


def _node_entry(node_axes: Sequence[str]):
    return tuple(node_axes) if len(node_axes) > 1 else node_axes[0]


def node_state_specs(cfg: DFLConfig, node_axes: Sequence[str]) -> DFLState:
    """The sparse engine's ``DFLState`` layout as PartitionSpec prefixes:
    node-stacked trees split over ``node_axes``, key and counter
    replicated."""
    from jax.sharding import PartitionSpec as P

    node = P(_node_entry(node_axes))
    return DFLState(params=node, opt_state=node,
                    hat_params=node if cfg.is_compressed else None,
                    rng=P(), round_idx=P())


def node_state_sharding(cfg: DFLConfig, mesh,
                        node_axes: Sequence[str]) -> DFLState:
    """``node_state_specs`` as ``NamedSharding``s on ``mesh`` — the
    ``jax.device_put`` / ``out_shardings`` target of a sparse-engine
    state."""
    from jax.sharding import NamedSharding

    specs = node_state_specs(cfg, node_axes)
    return DFLState(*(None if s is None else NamedSharding(mesh, s)
                      for s in specs))


def make_sharded_round_fn(
    cfg: DFLConfig,
    loss_fn: Callable,
    opt,
    mesh,
    *,
    node_axes: Sequence[str] = ("data",),
    use_kernels: bool = False,
    dynamic_taus: bool = False,
    participation: bool = False,
    constrain=None,
) -> Callable[..., Tuple[DFLState, dict]]:
    """Sparse-gossip round; call under jax.jit. State leaves carry the
    stacked node dim sharded over ``node_axes`` (local size 1).

    ``dynamic_taus``: round_fn(state, batches, tau1, tau2) with replicated
    int32 step-count scalars riding through the shard_map boundary;
    cfg.tau1/cfg.tau2 are the compiled maxima (see core.dfl.make_round_fn).
    The trip counts are identical on every node shard — whether broadcast
    from two device scalars or sliced per round from a [K, 2] trajectory
    scanned as xs (``core.executor.dispatch_trajectory``) — so the
    per-shift ppermutes inside the dynamic loops stay collectively matched.

    ``participation``: round_fn(state, batches, tau1, tau2, node_mask,
    edge_mask) — the masks ride through the shard_map boundary REPLICATED
    (P()), like the tau scalars: every node sees the full [N]/[E] vectors
    and takes its local view via ``ShardedSubstrate.node_mask_local`` /
    ``shift_masks``. The ppermutes still run on masked edges (masks gate
    accumulation weights, not collectives), so the program stays
    collectively matched and mask changes never retrace.

    ``constrain``: the dense engine's stacked-param sharding re-assertion.
    The sparse engine cannot honor it on its auto (GSPMD) axes — the specs
    name the manual node axes, and shard_map strips those — so a mesh with
    a >1-sized auto axis RAISES here rather than silently dropping the
    constraint (the silent drop was only ever safe because such meshes
    fall back to dense on the pinned jaxlib; see ROADMAP). Size-1 auto
    axes carry nothing to re-assert, so the argument is accepted and
    ignored there.
    """
    from jax.sharding import PartitionSpec as P

    topo = cfg.topology
    if constrain is not None:
        unconstrained = [a for a in mesh.axis_names
                        if a not in node_axes and mesh.shape[a] > 1]
        if unconstrained:
            raise NotImplementedError(
                "the sparse engine drops the `constrain` sharding "
                f"re-assertion on its auto (GSPMD) mesh axes "
                f"{unconstrained}: GSPMD may then resolve scan carries / "
                "vmapped grads to replicated and all-gather entire stacked "
                "weight trees (core.dfl._local_updates). Use the dense "
                "engine on tensor-parallel meshes, or teach "
                "ShardedSubstrate an auto-axis constrain first.")
    assert topo.is_shift_structured(), (
        f"{topo.name} is not circulant; use the dense engine "
        "(core.dfl.make_round_fn) for arbitrary topologies")
    mesh_n = substrate_lib.mesh_axis_size(mesh, tuple(node_axes))
    assert mesh_n == topo.num_nodes, (
        f"node mesh axes {tuple(node_axes)} enumerate {mesh_n} devices but "
        f"{topo.name} has {topo.num_nodes} nodes — the size-1-per-node "
        "shard_map layout would silently drop nodes")
    node_entry = _node_entry(node_axes)
    state_specs = node_state_specs(cfg, node_axes)
    batch_spec = P(None, node_entry)

    def body(state: DFLState, batches: PyTree, taus=None, masks=None):
        # local leaves: params [1, ...]; batches [tau1, 1, B, ...]
        squeeze = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
        unsqueeze = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        sub = ShardedSubstrate(topo, node_axes, use_kernels=use_kernels)
        params, opt_state, hat, metrics = round_body(
            cfg, loss_fn, opt, sub,
            squeeze(state.params),
            squeeze(state.opt_state),
            squeeze(state.hat_params) if cfg.is_compressed else None,
            state.rng, state.round_idx,
            # drop the local (size-1) node dim, keeping the leading tau1 dim
            jax.tree_util.tree_map(lambda x: x[:, 0], batches),
            taus=taus, masks=masks)
        new_state = DFLState(
            params=unsqueeze(params),
            opt_state=unsqueeze(opt_state),
            hat_params=unsqueeze(hat) if cfg.is_compressed else None,
            rng=None,  # typed key re-attached outside (see below)
            round_idx=state.round_idx + 1,
        )
        return new_state, metrics

    # The base PRNG key never advances (the folding discipline derives all
    # keys from round_idx), so it is NOT returned through the shard_map
    # boundary: XLA rejects partially-manual shardings on the typed key's
    # trailing u32[2] layout. It rides through as None and is re-attached.
    out_specs = (state_specs._replace(rng=None), P())

    if participation:
        assert dynamic_taus, (
            "participation masks ride the dynamic schedule-as-data path")
        mapped = substrate_lib.shard_map(
            lambda st, b, t1, t2, nm, em: body(st, b, (t1, t2), (nm, em)),
            mesh, (state_specs, batch_spec, P(), P(), P(), P()), out_specs,
            manual_axes=tuple(node_axes), check=False)

        def round_fn(state: DFLState, batches: PyTree, tau1, tau2,
                     node_mask, edge_mask):
            new_state, metrics = mapped(
                state, batches, jnp.asarray(tau1, jnp.int32),
                jnp.asarray(tau2, jnp.int32),
                jnp.asarray(node_mask, jnp.int32),
                jnp.asarray(edge_mask, jnp.int32))
            return new_state._replace(rng=state.rng), metrics

        return round_fn

    if dynamic_taus:
        mapped = substrate_lib.shard_map(
            lambda st, b, t1, t2: body(st, b, (t1, t2)),
            mesh, (state_specs, batch_spec, P(), P()), out_specs,
            manual_axes=tuple(node_axes), check=False)

        def round_fn(state: DFLState, batches: PyTree, tau1, tau2):
            new_state, metrics = mapped(
                state, batches, jnp.asarray(tau1, jnp.int32),
                jnp.asarray(tau2, jnp.int32))
            return new_state._replace(rng=state.rng), metrics

        return round_fn

    mapped = substrate_lib.shard_map(
        body, mesh, (state_specs, batch_spec), out_specs,
        manual_axes=tuple(node_axes), check=False)

    def round_fn(state: DFLState, batches: PyTree):
        new_state, metrics = mapped(state, batches)
        return new_state._replace(rng=state.rng), metrics

    return round_fn


def make_sharded_pipeline_fns(
    cfg: DFLConfig,
    loss_fn: Callable,
    opt,
    mesh,
    *,
    node_axes: Sequence[str] = ("data",),
    use_kernels: bool = False,
    participation: bool = False,
    constrain=None,
):
    """Sparse-engine pipelined-round pair behind
    ``core.dfl.make_pipeline_fns(..., engine="sparse")`` — the shard_map
    plumbing for ``pipeline_round_body`` / ``pipeline_drain_body``
    (signatures documented there). The in-flight gossip buffer ``buf`` is a
    params-like tree sharded over the node axes; ``have`` / ``prev_tau2``
    and the masks ride REPLICATED (P()) exactly like the dynamic round
    path's tau scalars, so the stale exchange's per-shift ppermutes stay
    collectively matched on every scan iteration (including the discarded
    first one). The base key rides through as None and is re-attached, as
    in ``make_sharded_round_fn``.
    """
    from jax.sharding import PartitionSpec as P

    topo = cfg.topology
    if constrain is not None:
        unconstrained = [a for a in mesh.axis_names
                        if a not in node_axes and mesh.shape[a] > 1]
        if unconstrained:
            raise NotImplementedError(
                "the sparse engine drops the `constrain` sharding "
                f"re-assertion on its auto (GSPMD) mesh axes "
                f"{unconstrained} (see make_sharded_round_fn)")
    assert topo.is_shift_structured(), (
        f"{topo.name} is not circulant; use the dense engine "
        "(core.dfl.make_pipeline_fns) for arbitrary topologies")
    mesh_n = substrate_lib.mesh_axis_size(mesh, tuple(node_axes))
    assert mesh_n == topo.num_nodes, (
        f"node mesh axes {tuple(node_axes)} enumerate {mesh_n} devices but "
        f"{topo.name} has {topo.num_nodes} nodes")
    node_entry = _node_entry(node_axes)
    state_specs = node_state_specs(cfg, node_axes)
    buf_spec = P(node_entry)
    batch_spec = P(None, node_entry)
    squeeze = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
    unsqueeze = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)

    def pipe_body(state: DFLState, buf, have, prev_tau2, batches, tau1,
                  node_mask=None, prev_edge_mask=None):
        sub = ShardedSubstrate(topo, node_axes, use_kernels=use_kernels)
        params, opt_state, hat, z, metrics = pipeline_round_body(
            cfg, loss_fn, opt, sub,
            squeeze(state.params),
            squeeze(state.opt_state),
            squeeze(state.hat_params) if cfg.is_compressed else None,
            state.rng, state.round_idx,
            squeeze(buf), have, tau1, prev_tau2,
            jax.tree_util.tree_map(lambda x: x[:, 0], batches),
            constrain, node_mask=node_mask, prev_edge_mask=prev_edge_mask)
        new_state = DFLState(
            params=unsqueeze(params),
            opt_state=unsqueeze(opt_state),
            hat_params=unsqueeze(hat) if cfg.is_compressed else None,
            rng=None,  # typed key re-attached outside (see round path)
            round_idx=state.round_idx + 1,
        )
        return new_state, unsqueeze(z), metrics

    def drain_body(state: DFLState, buf, prev_tau2, prev_edge_mask=None):
        sub = ShardedSubstrate(topo, node_axes, use_kernels=use_kernels)
        params, hat = pipeline_drain_body(
            cfg, sub,
            squeeze(state.params),
            squeeze(state.hat_params) if cfg.is_compressed else None,
            state.rng, state.round_idx,
            squeeze(buf), prev_tau2, constrain,
            prev_edge_mask=prev_edge_mask)
        return DFLState(
            params=unsqueeze(params),
            opt_state=state.opt_state,
            hat_params=unsqueeze(hat) if cfg.is_compressed else None,
            rng=None,
            round_idx=state.round_idx,
        )

    pipe_out = (state_specs._replace(rng=None), buf_spec, P())
    drain_out = state_specs._replace(rng=None)

    if participation:
        pipe_mapped = substrate_lib.shard_map(
            lambda st, bf, hv, pt2, pem, b, t1, nm: pipe_body(
                st, bf, hv, pt2, b, t1, node_mask=nm, prev_edge_mask=pem),
            mesh,
            (state_specs, buf_spec, P(), P(), P(), batch_spec, P(), P()),
            pipe_out, manual_axes=tuple(node_axes), check=False)
        drain_mapped = substrate_lib.shard_map(
            lambda st, bf, pt2, pem: drain_body(
                st, bf, pt2, prev_edge_mask=pem),
            mesh, (state_specs, buf_spec, P(), P()), drain_out,
            manual_axes=tuple(node_axes), check=False)

        def pipe_fn(state, buf, have, prev_tau2, prev_edge_mask, batches,
                    tau1, node_mask):
            new_state, z, metrics = pipe_mapped(
                state, buf, jnp.asarray(have, jnp.int32),
                jnp.asarray(prev_tau2, jnp.int32),
                jnp.asarray(prev_edge_mask, jnp.int32), batches,
                jnp.asarray(tau1, jnp.int32),
                jnp.asarray(node_mask, jnp.int32))
            return new_state._replace(rng=state.rng), z, metrics

        def drain_fn(state, buf, prev_tau2, prev_edge_mask):
            new_state = drain_mapped(
                state, buf, jnp.asarray(prev_tau2, jnp.int32),
                jnp.asarray(prev_edge_mask, jnp.int32))
            return new_state._replace(rng=state.rng)

        return pipe_fn, drain_fn

    pipe_mapped = substrate_lib.shard_map(
        lambda st, bf, hv, pt2, b, t1: pipe_body(st, bf, hv, pt2, b, t1),
        mesh, (state_specs, buf_spec, P(), P(), batch_spec, P()),
        pipe_out, manual_axes=tuple(node_axes), check=False)
    drain_mapped = substrate_lib.shard_map(
        lambda st, bf, pt2: drain_body(st, bf, pt2),
        mesh, (state_specs, buf_spec, P()), drain_out,
        manual_axes=tuple(node_axes), check=False)

    def pipe_fn(state, buf, have, prev_tau2, batches, tau1):
        new_state, z, metrics = pipe_mapped(
            state, buf, jnp.asarray(have, jnp.int32),
            jnp.asarray(prev_tau2, jnp.int32), batches,
            jnp.asarray(tau1, jnp.int32))
        return new_state._replace(rng=state.rng), z, metrics

    def drain_fn(state, buf, prev_tau2):
        new_state = drain_mapped(state, buf,
                                 jnp.asarray(prev_tau2, jnp.int32))
        return new_state._replace(rng=state.rng)

    return pipe_fn, drain_fn
