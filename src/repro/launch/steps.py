"""Builders that assemble (jitted fn, abstract args) pairs for every
(architecture x input shape x mesh) combination.

For training shapes three functions are built:
  * ``round``   — one full DFL round (tau1 local scans + tau2 gossip):
                  the compile-proof artifact of the dry-run.
  * ``local``   — ONE local SGD step on all nodes: the roofline compute unit.
  * ``gossip``  — ONE gossip (mixing) step: the roofline collective unit.
Roofline terms compose analytically: round = tau1*local + tau2*gossip,
sidestepping XLA cost_analysis' while-loop trip-count blindness.

For serving shapes: ``prefill`` / ``decode`` steps.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape, SHAPES
from repro.core import dfl as dfl_lib
from repro.core import mixing as mixing_lib
from repro.core import substrate as substrate_lib
from repro.core import topology as topo_lib
from repro.core.compression import Compressor
from repro.launch import sharding as shard_lib
from repro.models import transformer as tf_lib
from repro.models.policy import activation_sharding
from repro.models.common import ModelConfig
from repro.optim import sgd

PyTree = Any

KEY_DTYPE = jax.eval_shape(lambda: jax.random.key(0)).dtype


@dataclasses.dataclass
class Built:
    """A jitted function plus the abstract args to lower it with."""

    fn: Callable
    args: Tuple
    meta: Dict[str, Any]
    ctx: Optional[Callable] = None   # context manager active during tracing

    def lower(self):
        if self.ctx is None:
            return self.fn.lower(*self.args)
        with self.ctx():
            return self.fn.lower(*self.args)


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------


def memory_tokens_for(cfg: ModelConfig, shape: InputShape) -> int:
    if cfg.arch_type == "audio":
        return max(16, shape.seq_len // 4)
    return cfg.memory_tokens


def kernelize_compressor(compression: Optional[Compressor],
                         use_kernels: bool) -> Optional[Compressor]:
    """``use_kernels`` engages the kernel-backed TopK on ANY engine: the
    sparse substrate fuses compress-and-move itself
    (``substrate.ShardedSubstrate.choco_step``), but the dense engine
    compresses through the Compressor instance — so carry the flag on it
    (the kernel TopK is bitwise-identical to the reference, see
    ``repro.core.compression.TopK``). Other compressors pass through."""
    from repro.core.compression import TopK

    if use_kernels and isinstance(compression, TopK):
        return dataclasses.replace(compression, use_kernels=True)
    return compression


def dfl_setup(arch: ArchConfig, mesh: Mesh, *, tau1: int, tau2: int,
              compression: Optional[Compressor], mixing_impl: str,
              topology: str = "ring"):
    mode = arch.sharding_mode
    n = shard_lib.num_nodes_for(mode, mesh, arch.fsdp_nodes)
    if n == 1:  # degenerate single-node mesh (host tests)
        topo = topo_lib.fully_connected(1)
    else:
        topo = {
            "ring": topo_lib.ring,
            "full": topo_lib.fully_connected,
            "torus": lambda k: (topo_lib.torus(2, k // 2) if k >= 4
                                else topo_lib.ring(k)),
        }[topology](n)
    dcfg = dfl_lib.DFLConfig(
        tau1=tau1, tau2=tau2, topology=topo,
        mixing_impl=mixing_impl, compression=compression)
    return mode, n, dcfg


def _abstract_state(arch: ArchConfig, cfg: ModelConfig, mesh: Mesh, mode: str,
                    n: int, opt, compressed: bool):
    params_abs, axes = tf_lib.init_params(cfg, jax.random.key(0), abstract=True)
    stacked = shard_lib.stack_node_dim_abstract(params_abs, n)
    opt_abs = jax.eval_shape(jax.vmap(opt.init), stacked)
    hat_abs = stacked if compressed else None
    state_abs = dfl_lib.DFLState(
        params=stacked,
        opt_state=opt_abs,
        hat_params=hat_abs,
        rng=jax.ShapeDtypeStruct((), KEY_DTYPE),
        round_idx=jax.ShapeDtypeStruct((), jnp.int32),
    )

    p_sh = shard_lib.params_shardings(axes, stacked, mode, mesh, node_dim=True)
    naxes = shard_lib.node_axes_for(mode, mesh)
    node_entry = (naxes if len(naxes) > 1 else naxes[0]) if naxes else None

    def opt_leaf_sh(leaf):
        if leaf.shape and leaf.shape[0] == n and node_entry is not None:
            return NamedSharding(mesh, P(node_entry))
        return shard_lib.replicated(mesh)

    opt_sh = jax.tree_util.tree_map(opt_leaf_sh, opt_abs)
    state_sh = dfl_lib.DFLState(
        params=p_sh,
        opt_state=opt_sh,
        hat_params=p_sh if compressed else None,
        rng=shard_lib.replicated(mesh),
        round_idx=shard_lib.replicated(mesh),
    )
    return state_abs, state_sh, axes


def _abstract_batch(arch: ArchConfig, cfg: ModelConfig, shape: InputShape,
                    mesh: Mesh, mode: str, n: int, tau1: Optional[int]):
    """Training batches [tau1?, N, B/N, ...]."""
    per_node = shape.global_batch // n
    assert per_node >= 1, (
        f"{arch.arch_id}/{shape.name}: global batch {shape.global_batch} < "
        f"{n} nodes")
    lead = (tau1,) if tau1 is not None else ()
    tok_shape = lead + (n, per_node, shape.seq_len)
    batch = {
        "tokens": jax.ShapeDtypeStruct(tok_shape, jnp.int32),
        "labels": jax.ShapeDtypeStruct(tok_shape, jnp.int32),
    }
    if cfg.has_memory_input:
        m = memory_tokens_for(cfg, shape)
        mem_dim = cfg.memory_dim or cfg.d_model
        batch["memory"] = jax.ShapeDtypeStruct(
            lead + (n, per_node, m, mem_dim), jnp.bfloat16)
    sh = shard_lib.batch_sharding(mesh, mode, has_tau_dim=tau1 is not None)
    batch_sh = {k: sh for k in batch}
    return batch, batch_sh


def _act_policy(mesh: Mesh, mode: str, kind: str):
    """Residual-stream sharding policy per mode/step kind (see policy.py).

    train gossip-dp  : [B,S,D] d_model over `model` (Megatron-style sharded
                       residual; batch is per-node, node dim rides `data`
                       via vmap). Sharding seq instead was tried first but
                       fights the flash-attention chunk reshape (nq < mesh
                       model size -> GSPMD all-gathers, 16 GiB/device).
    train gossip-fsdp: batch over `data`, d_model over `model`.
    prefill          : batch over `data`(x`pod`), d_model over `model`.
    decode           : batch over `data`(x`pod`) only (S=1).
    """
    has_pod = "pod" in mesh.axis_names
    data_entry = ("pod", "data") if has_pod else "data"
    if kind == "train":
        if mode == "gossip-dp":
            return lambda: activation_sharding(mesh, embed="model")
        return lambda: activation_sharding(mesh, batch="data", embed="model")
    if kind == "prefill":
        return lambda: activation_sharding(mesh, batch=data_entry,
                                           embed="model")
    return lambda: activation_sharding(mesh, batch=data_entry)


def _make_constrain(sharding_tree):
    """Re-assert stacked-param shardings (applied to grads/params inside the
    round; prevents GSPMD from replicating scan carries)."""

    def constrain(tree):
        return jax.tree_util.tree_map(
            lambda x, sh: jax.lax.with_sharding_constraint(x, sh), tree,
            sharding_tree)

    return constrain


# ---------------------------------------------------------------------------
# Training builders
# ---------------------------------------------------------------------------


def select_engine(engine: str, dcfg, mesh: Mesh, mode: str) -> str:
    """Resolve the round-engine choice (see core.sharded module docstring).

    "auto" picks the sparse (shard_map + ppermute, deg neighbor copies)
    engine whenever the topology is shift-structured (circulant) AND the
    node mesh axes enumerate all N > 1 nodes (gossip-dp); everything else —
    single-node host meshes, replicated-node gossip-fsdp, non-circulant
    topologies, dense-only features — falls back to the dense engine.
    """
    if engine != "auto":
        return engine
    node_axes = shard_lib.node_axes_for(mode, mesh)
    if not node_axes:
        return "dense"
    # Train rounds built here always re-assert stacked-param shardings
    # (``_make_constrain``); the sparse engine refuses a constrain on
    # meshes with >1-sized auto (GSPMD) axes rather than silently dropping
    # it (core.sharded), so auto-selection must not steer those meshes
    # into the raise — dense stays the tensor-parallel path until the
    # sharded engine grows an auto-axis constrain.
    if any(mesh.shape[a] > 1 for a in mesh.axis_names
           if a not in node_axes):
        return "dense"
    return ("sparse"
            if dfl_lib.sparse_engine_eligible(dcfg, mesh, node_axes)
            else "dense")


def roofline_cost_inputs(
    arch: ArchConfig,
    shape_name: str,
    mesh: Mesh,
    *,
    topology: str = "ring",
    reduced: bool = False,
) -> Dict[str, float]:
    """MEASURED planner cost inputs from compiled XLA artifacts.

    Lowers+compiles the unit steps (``build_local_step`` /
    ``build_gossip_step``) and reads the roofline terms off the optimized
    HLO (``launch.roofline``): ``step_flops`` is one local step's FLOPs
    PER NODE — the roofline's per-device number rescaled by
    mesh.size / N, since the per-device program carries all N vmapped
    node updates split over mesh.size devices (on gossip-dp meshes,
    nodes == devices and the factor is the model-parallel share; on a
    1-device host mesh it divides the stacked work back out) — matching
    ``ComputeModel.step_flops``'s one-node contract.
    ``gossip_collective_bytes`` is one gossip step's per-device
    collective bytes. These replace the planner's a-priori 6*P*tokens /
    fp32-tree estimates — the same numbers, measured instead of assumed
    (``plan_train_schedule(..., use_roofline=True)``).

    ``gossip_collective_bytes`` is 0.0 when the lowering emits no
    collectives (single-device host meshes mix in registers); callers must
    fall back to the analytic wire size then.
    """
    from repro.launch import roofline as roof_lib

    n = shard_lib.num_nodes_for(arch.sharding_mode, mesh, arch.fsdp_nodes)
    local = build_local_step(arch, shape_name, mesh, reduced=reduced)
    la = roof_lib.analyze_compiled(local.lower().compile(),
                                   chips=mesh.size)
    gossip = build_gossip_step(arch, mesh, topology=topology,
                               reduced=reduced)
    ga = roof_lib.analyze_compiled(gossip.lower().compile(),
                                   chips=mesh.size)
    return {
        "step_flops": float(la["roofline"]["flops"]) * mesh.size / max(n, 1),
        "step_hbm_bytes": float(la["roofline"]["hbm_bytes"]),
        "gossip_collective_bytes": float(
            ga["roofline"]["collective_bytes"]),
        "nodes": n,
    }


def plan_train_schedule(
    arch: ArchConfig,
    shape_name: str,
    mesh: Mesh,
    *,
    budget_s: float,
    topology: str = "ring",
    compression: Optional[Compressor] = None,
    flops_per_s: Optional[float] = None,
    link_bytes_per_s: Optional[float] = None,
    sigma: float = 1.0,
    f_gap: float = 1.0,
    reduced: bool = False,
    grid=None,
    wire_engine: str = "auto",
    use_roofline: bool = False,
):
    """Pick (tau1, tau2) for a (arch, shape, mesh) deployment with the
    planner (``repro.planner``) before building anything.

    By default the compute side is priced analytically — 6 * params *
    tokens FLOPs per local step per node at the chip's bf16 peak — and the
    gossip side from the model's fp32 wire size over one ICI link; the
    same first-order estimates the roofline uses. With
    ``use_roofline=True`` both sides come MEASURED off the compiled HLO
    instead (``roofline_cost_inputs``): the local step's actual per-NODE
    FLOPs, and the gossip step's actual collective bytes folded back into
    an effective per-copy wire size (so wire-bit budgets price what the
    lowering really ships; falls back to the analytic size when the
    lowering has no collectives — e.g. single-device host meshes — or
    when a ``compression`` is set, since the compressor's model_dim is
    derived from model_bits). Returns
    the planner ``Plan``; ``build_planned_round`` turns it straight into a
    Built round.
    """
    from repro.launch import mesh as mesh_lib
    from repro.planner import (Budget, ComputeModel, CostModel, LinkModel,
                               plan)

    cfg = arch.reduced if reduced else arch.model
    shape = SHAPES[shape_name]
    _mode, n, dcfg = dfl_setup(arch, mesh, tau1=1, tau2=1,
                               compression=compression,
                               mixing_impl="dense", topology=topology)
    params = cfg.param_count()
    tokens_per_node = shape.global_batch * shape.seq_len / max(n, 1)
    step_flops = 6.0 * params * tokens_per_node
    model_bits = 32.0 * params
    if use_roofline:
        measured = roofline_cost_inputs(arch, shape_name, mesh,
                                        topology=topology, reduced=reduced)
        step_flops = measured["step_flops"]
        copies = mixing_lib.gossip_copies_per_step(dcfg.topology,
                                                   wire_engine)
        if (measured["gossip_collective_bytes"] > 0.0 and copies > 0
                and compression is None):
            # effective per-copy wire size: what the compiled gossip step
            # actually moves, spread over the engine's copy count, so
            # round_cost's copies * model_bits reproduces the measurement.
            # Compressed planning keeps the analytic size: the planner
            # derives the compressor's model_dim from model_bits/32, so
            # overriding it with wire bytes would corrupt delta/zeta.
            model_bits = (8.0 * measured["gossip_collective_bytes"]
                          / copies)
    cost_model = CostModel(
        compute=ComputeModel(
            step_flops=step_flops,
            flops_per_s=flops_per_s or mesh_lib.PEAK_FLOPS_BF16),
        link=LinkModel(
            bytes_per_s=link_bytes_per_s or mesh_lib.ICI_BW),
        topology=dcfg.topology,
        model_bits=model_bits,
        engine=wire_engine)
    kw = dict(sigma=sigma, f_gap=f_gap)
    if grid is not None:
        kw["grid"] = grid
    if compression is not None:
        kw["compressors"] = (compression,)
    return plan(Budget(wall_clock_s=budget_s), cost_model, **kw)


def build_planned_round(
    arch: ArchConfig,
    shape_name: str,
    mesh: Mesh,
    *,
    budget_s: float,
    topology: str = "ring",
    compression: Optional[Compressor] = None,
    reduced: bool = False,
    **plan_kw,
) -> Built:
    """``build_train_round`` with (tau1, tau2) chosen by the planner; the
    chosen Plan's knobs and prediction land in ``meta["plan"]``."""
    p = plan_train_schedule(
        arch, shape_name, mesh, budget_s=budget_s, topology=topology,
        compression=compression, reduced=reduced, **plan_kw)
    built = build_train_round(
        arch, shape_name, mesh, tau1=p.tau1, tau2=p.tau2,
        compression=p.compressor, topology=topology, reduced=reduced)
    built.meta["plan"] = {
        "tau1": p.tau1, "tau2": p.tau2, "eta": p.eta,
        "compressor": p.compressor_name, "rounds": p.rounds,
        "predicted_bound": p.predicted_bound,
        "round_time_s": p.round_cost.time_s,
        "round_wire_bits": p.round_cost.wire_bits,
        "budget_s": budget_s,
        "use_roofline": bool(plan_kw.get("use_roofline", False)),
    }
    return built


def build_train_round(
    arch: ArchConfig,
    shape_name: str,
    mesh: Mesh,
    *,
    tau1: int = 4,
    tau2: int = 4,
    compression: Optional[Compressor] = None,
    mixing_impl: str = "dense",
    topology: str = "ring",
    lr: float = 1e-3,
    reduced: bool = False,
    engine: str = "auto",
    use_kernels: bool = False,
) -> Built:
    cfg = arch.reduced if reduced else arch.model
    shape = SHAPES[shape_name]
    compression = kernelize_compressor(compression, use_kernels)
    mode, n, dcfg = dfl_setup(arch, mesh, tau1=tau1, tau2=tau2,
                              compression=compression,
                              mixing_impl=mixing_impl, topology=topology)
    opt = sgd(lr)
    loss_fn = lambda p, b, k: tf_lib.train_loss(p, b, cfg, k)
    state_abs, state_sh, _ = _abstract_state(
        arch, cfg, mesh, mode, n, opt, compressed=dcfg.is_compressed)
    constrain = _make_constrain(state_sh.params)
    engine = select_engine(engine, dcfg, mesh, mode)
    round_fn = dfl_lib.make_round_fn(
        dcfg, loss_fn, opt, constrain=constrain, engine=engine, mesh=mesh,
        node_axes=shard_lib.node_axes_for(mode, mesh),
        use_kernels=use_kernels)
    batch_abs, batch_sh = _abstract_batch(arch, cfg, shape, mesh, mode, n, tau1)
    fn = jax.jit(
        round_fn,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )
    return Built(fn, (state_abs, batch_abs), {
        "kind": "round", "arch": arch.arch_id, "shape": shape_name,
        "mode": mode, "nodes": n, "tau1": tau1, "tau2": tau2,
        "mixing": mixing_impl, "engine": engine,
        "compressed": dcfg.is_compressed,
    }, ctx=_act_policy(mesh, mode, "train"))


def build_train_superstep(
    arch: ArchConfig,
    shape_name: str,
    mesh: Mesh,
    *,
    rounds: int = 4,
    tau1_max: int = 8,
    tau2_max: int = 8,
    compression: Optional[Compressor] = None,
    topology: str = "ring",
    lr: float = 1e-3,
    reduced: bool = False,
    engine: str = "auto",
    use_kernels: bool = False,
    overlap: str = "none",
) -> Built:
    """The fused K-round superstep as a lowerable production artifact.

    One executable covers EVERY length-K schedule trajectory within
    (tau1_max, tau2_max): the schedule is a replicated [K, 2] int32 device
    array scanned as ``lax.scan`` xs alongside the batches, so round k
    runs (taus[k, 0], taus[k, 1]) dynamic trip counts
    (``make_round_fn(dynamic_taus=True)``) and a heterogeneous per-round
    schedule costs zero extra compiles over a uniform one. The ``DFLState``
    carry is DONATED (params+opt buffers aliased in place — the
    peak-memory fix the per-round jit was missing) and the per-round
    metrics come back stacked [K], tagged with the realized tau1/tau2
    rows, so the host syncs once per superstep. Batch leaves are
    [K, tau1_max, N, B, ...] with rows >= taus[k, 0] never read. This is
    the compile-proof artifact of what
    ``repro.core.executor.RoundExecutor.dispatch_trajectory`` dispatches
    at runtime.

    ``overlap="pipeline"`` lowers the double-buffered variant instead
    (``dfl.make_pipeline_fns`` scanned by
    ``executor.make_pipeline_superstep``): round k's gossip exchange is
    issued alongside round k+1's local phase and folded one round late,
    with the final in-flight exchange drained inside the same executable.
    Same signature, same [K, 2] row layout, one-round-stale mixing
    semantics (docs/ARCHITECTURE.md "Overlapped execution").
    """
    if overlap not in ("none", "pipeline"):
        raise ValueError(
            f"overlap must be 'none' or 'pipeline', got {overlap!r}")
    cfg = arch.reduced if reduced else arch.model
    shape = SHAPES[shape_name]
    compression = kernelize_compressor(compression, use_kernels)
    mode, n, dcfg = dfl_setup(arch, mesh, tau1=tau1_max, tau2=tau2_max,
                              compression=compression,
                              mixing_impl="dense", topology=topology)
    opt = sgd(lr)
    loss_fn = lambda p, b, k: tf_lib.train_loss(p, b, cfg, k)
    state_abs, state_sh, _ = _abstract_state(
        arch, cfg, mesh, mode, n, opt, compressed=dcfg.is_compressed)
    constrain = _make_constrain(state_sh.params)
    engine = select_engine(engine, dcfg, mesh, mode)
    if overlap == "pipeline":
        from repro.core.executor import make_pipeline_superstep

        pipe_fn, drain_fn = dfl_lib.make_pipeline_fns(
            dcfg, loss_fn, opt, constrain=constrain, engine=engine,
            mesh=mesh, node_axes=shard_lib.node_axes_for(mode, mesh),
            use_kernels=use_kernels)
        superstep = make_pipeline_superstep(pipe_fn, drain_fn)
    else:
        round_fn = dfl_lib.make_round_fn(
            dcfg, loss_fn, opt, constrain=constrain, engine=engine,
            mesh=mesh, node_axes=shard_lib.node_axes_for(mode, mesh),
            use_kernels=use_kernels, dynamic_taus=True)

        def superstep(state, batches, taus):
            def body(st, xs):
                b, tau = xs
                st, metrics = round_fn(st, b, tau[0], tau[1])
                return st, dict(metrics, tau1=tau[0], tau2=tau[1])

            return jax.lax.scan(body, state, (batches, taus))

    batch_abs, batch_sh = _abstract_batch(arch, cfg, shape, mesh, mode, n,
                                          tau1_max)
    # prepend the K (rounds) dim: replicated, like the tau1 dim.
    batch_abs = {k: jax.ShapeDtypeStruct((rounds,) + v.shape, v.dtype)
                 for k, v in batch_abs.items()}
    batch_sh = {k: NamedSharding(mesh, P(None, *sh.spec))
                for k, sh in batch_sh.items()}
    taus_abs = jax.ShapeDtypeStruct((rounds, 2), jnp.int32)
    fn = jax.jit(
        superstep,
        in_shardings=(state_sh, batch_sh, shard_lib.replicated(mesh)),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )
    return Built(fn, (state_abs, batch_abs, taus_abs), {
        "kind": "superstep", "arch": arch.arch_id, "shape": shape_name,
        "mode": mode, "nodes": n, "rounds": rounds,
        "tau1_max": tau1_max, "tau2_max": tau2_max, "engine": engine,
        "schedule": "trajectory", "overlap": overlap,
        "compressed": dcfg.is_compressed,
    }, ctx=_act_policy(mesh, mode, "train"))


def build_local_step(
    arch: ArchConfig, shape_name: str, mesh: Mesh, *,
    lr: float = 1e-3, reduced: bool = False,
) -> Built:
    """ONE local SGD step on all nodes (roofline compute unit)."""
    cfg = arch.reduced if reduced else arch.model
    shape = SHAPES[shape_name]
    mode, n, _ = dfl_setup(arch, mesh, tau1=1, tau2=1, compression=None,
                           mixing_impl="dense")
    opt = sgd(lr)
    state_abs, state_sh, _ = _abstract_state(
        arch, cfg, mesh, mode, n, opt, compressed=False)
    batch_abs, batch_sh = _abstract_batch(arch, cfg, shape, mesh, mode, n, None)

    constrain = _make_constrain(state_sh.params)

    def local_step(params, opt_state, batch):
        def loss_one(p, b):
            return tf_lib.train_loss(p, b, cfg)
        (losses, _), grads = jax.vmap(
            jax.value_and_grad(loss_one, has_aux=True))(params, batch)
        grads = constrain(grads)
        updates, opt_state = jax.vmap(opt.update)(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), params, updates)
        return params, opt_state, jnp.mean(losses)

    fn = jax.jit(
        local_step,
        in_shardings=(state_sh.params, state_sh.opt_state, batch_sh),
        out_shardings=(state_sh.params, state_sh.opt_state, None),
        donate_argnums=(0, 1),
    )
    return Built(fn, (state_abs.params, state_abs.opt_state, batch_abs), {
        "kind": "local", "arch": arch.arch_id, "shape": shape_name,
        "mode": mode, "nodes": n,
    }, ctx=_act_policy(mesh, mode, "train"))


def build_gossip_step(
    arch: ArchConfig, mesh: Mesh, *,
    mixing_impl: str = "dense",
    topology: str = "ring",
    compression: Optional[Compressor] = None,
    reduced: bool = False,
) -> Built:
    """ONE gossip step over the stacked params (roofline collective unit)."""
    cfg = arch.reduced if reduced else arch.model
    mode, n, dcfg = dfl_setup(arch, mesh, tau1=1, tau2=1,
                              compression=compression,
                              mixing_impl="dense", topology=topology)
    opt = sgd(1e-3)
    state_abs, state_sh, _ = _abstract_state(
        arch, cfg, mesh, mode, n, opt, compressed=compression is not None)

    if compression is None:
        def gossip_step(params):
            return mixing_lib.mix_dense(params, dcfg.topology)

        fn = jax.jit(gossip_step, in_shardings=(state_sh.params,),
                     out_shardings=state_sh.params, donate_argnums=(0,))
        args = (state_abs.params,)
    else:
        def gossip_step(params, hat, key):
            from repro.core.dfl import _communicate_choco
            c = dataclasses.replace(dcfg, tau2=1)
            return _communicate_choco(c, params, hat, key)

        fn = jax.jit(
            gossip_step,
            in_shardings=(state_sh.params, state_sh.params, None),
            out_shardings=(state_sh.params, state_sh.params),
            donate_argnums=(0, 1))
        args = (state_abs.params, state_abs.params,
                jax.ShapeDtypeStruct((), KEY_DTYPE))
    return Built(fn, args, {
        "kind": "gossip", "arch": arch.arch_id, "mode": mode, "nodes": n,
        "mixing": mixing_impl,
        "compressed": compression is not None,
    })


# ---------------------------------------------------------------------------
# Serving builders
# ---------------------------------------------------------------------------


def _serve_param_shardings(arch: ArchConfig, cfg: ModelConfig, mesh: Mesh):
    params_abs, axes = tf_lib.init_params(cfg, jax.random.key(0), abstract=True)
    mode = arch.sharding_mode  # fsdp archs shard embed over data at serve too
    p_sh = shard_lib.params_shardings(axes, params_abs, mode, mesh,
                                      node_dim=False)
    return params_abs, p_sh


def _batch_entry(mesh: Mesh, batch: int):
    axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    size = substrate_lib.mesh_axis_size(mesh, axes)
    if batch % size == 0:
        return axes if len(axes) > 1 else axes[0]
    if batch % mesh.shape["data"] == 0:
        return "data"
    return None


def decode_state_shardings(cfg: ModelConfig, state_abs, mesh: Mesh,
                           batch_entry, seq_entry):
    """Sharding tree matching a DecodeState, by leaf geometry."""

    def cache_leaf(leaf):
        shp = leaf.shape[1:]  # strip the stacked periods dim
        if len(shp) == 4:        # kv cache [B, T, KVH, hd]
            return NamedSharding(mesh, P(None, batch_entry, seq_entry))
        if len(shp) == 1:        # pos [T]
            return NamedSharding(mesh, P(None, seq_entry))
        if len(shp) == 3 and shp[1:] == (cfg.d_inner, cfg.ssm_state):
            model_ok = cfg.d_inner % mesh.shape["model"] == 0
            return NamedSharding(
                mesh, P(None, batch_entry, "model" if model_ok else None))
        if len(shp) == 3:        # conv state [B, K-1, di]
            model_ok = shp[-1] % mesh.shape["model"] == 0
            return NamedSharding(
                mesh, P(None, batch_entry, None, "model" if model_ok else None))
        return shard_lib.replicated(mesh)

    caches_sh = tuple(
        jax.tree_util.tree_map(cache_leaf, c) for c in state_abs.caches)
    mem_sh = (NamedSharding(mesh, P(batch_entry, None, None))
              if state_abs.memory is not None else None)
    return tf_lib.DecodeState(
        caches=caches_sh, memory=mem_sh, position=shard_lib.replicated(mesh))


def build_prefill(arch: ArchConfig, shape_name: str, mesh: Mesh, *,
                  reduced: bool = False) -> Built:
    cfg = arch.reduced if reduced else arch.model
    shape = SHAPES[shape_name]
    params_abs, p_sh = _serve_param_shardings(arch, cfg, mesh)
    b = shape.global_batch
    batch_entry = _batch_entry(mesh, b)
    batch = {"tokens": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32)}
    batch_sh = {"tokens": NamedSharding(mesh, P(batch_entry, None))}
    if cfg.has_memory_input:
        m = memory_tokens_for(cfg, shape)
        mem_dim = cfg.memory_dim or cfg.d_model
        batch["memory"] = jax.ShapeDtypeStruct((b, m, mem_dim), jnp.bfloat16)
        batch_sh["memory"] = NamedSharding(mesh, P(batch_entry, None, None))

    def prefill_step(params, batch):
        return tf_lib.prefill(params, batch, cfg, max_len=shape.seq_len)

    fn = jax.jit(prefill_step, in_shardings=(p_sh, batch_sh))
    return Built(fn, (params_abs, batch), {
        "kind": "prefill", "arch": arch.arch_id, "shape": shape_name,
        "batch": b, "seq": shape.seq_len,
    }, ctx=_act_policy(mesh, arch.sharding_mode, "prefill"))


def build_decode(arch: ArchConfig, shape_name: str, mesh: Mesh, *,
                 reduced: bool = False,
                 seq_shard: Optional[Any] = "auto") -> Built:
    cfg = arch.reduced if reduced else arch.model
    shape = SHAPES[shape_name]
    params_abs, p_sh = _serve_param_shardings(arch, cfg, mesh)
    b = shape.global_batch
    batch_entry = _batch_entry(mesh, b)
    if seq_shard == "auto":
        # baseline: KV-cache sequence dim over `model` (works for every
        # GQA head count); long-context batch=1 leaves `data` idle (a
        # hillclimb target, see EXPERIMENTS.md section Perf).
        seq_entry = "model"
    else:
        seq_entry = seq_shard
    state_abs = tf_lib.init_decode_state(cfg, b, shape.seq_len, abstract=True)
    if cfg.has_memory_input:
        m = memory_tokens_for(cfg, shape)
        state_abs = state_abs._replace(memory=jax.ShapeDtypeStruct(
            (b, m, cfg.d_model), cfg.dtype))
    state_sh = decode_state_shardings(cfg, state_abs, mesh, batch_entry,
                                      seq_entry)
    tokens_abs = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    tokens_sh = NamedSharding(mesh, P(batch_entry, None))

    def serve_step(params, state, tokens):
        return tf_lib.decode_step(params, state, tokens, cfg)

    fn = jax.jit(
        serve_step,
        in_shardings=(p_sh, state_sh, tokens_sh),
        out_shardings=(None, state_sh),
        donate_argnums=(1,),
    )
    return Built(fn, (params_abs, state_abs, tokens_abs), {
        "kind": "decode", "arch": arch.arch_id, "shape": shape_name,
        "batch": b, "seq": shape.seq_len, "seq_entry": str(seq_entry),
    }, ctx=_act_policy(mesh, arch.sharding_mode, "decode"))


def build_for(arch: ArchConfig, shape_name: str, mesh: Mesh, **kw) -> Built:
    """The headline function for a (arch, shape, mesh) combination."""
    kind = SHAPES[shape_name].kind
    if kind == "train":
        return build_train_round(arch, shape_name, mesh, **kw)
    if kind == "prefill":
        return build_prefill(arch, shape_name, mesh,
                             reduced=kw.get("reduced", False))
    return build_decode(arch, shape_name, mesh,
                        reduced=kw.get("reduced", False))
