"""The DFL / C-DFL algorithm engine (paper Algorithms 1 and 2).

A *round* is tau1 local SGD steps followed by tau2 gossip steps:

    local update (t in [k]_1):   X_{t+1} = X_t - eta G_t          (Alg. 1 l.4)
    communication (t in [k]_2):  X_{t+1} = X_t C                  (Alg. 1 l.6)

With compression (C-DFL, Alg. 2), the communication sub-round becomes the
CHOCO-G error-feedback iteration over the shared estimates Y = [w_hat^(i)]:

    X <- X + gamma * Y (C - I)                                    (Alg. 2 l.6)
    q  = Q(X - Y)                                                 (Alg. 2 l.7)
    Y <- Y + q                                                    (Alg. 2 l.11)

The algorithm (local-update scan, CHOCO-G step, RNG folding, metrics) is
written ONCE here against the ``repro.core.substrate`` node abstraction and
executed by two engines, selected via ``make_round_fn(..., engine=...)``:

  * ``"dense"``  — every parameter leaf carries a leading node dimension of
                   size N; gossip is the X C einsum (any topology). Pure
                   jit/vmap/scan; distribution is decided by the caller via
                   shardings on the stacked arrays (see ``repro.launch``).
  * ``"sparse"`` — nodes live on manual mesh axes inside ``shard_map``;
                   gossip is per-shift ``ppermute`` (circulant C only, deg
                   neighbor copies instead of N-1). Built by
                   ``repro.core.sharded.make_sharded_round_fn``.
  * ``"auto"``   — sparse iff a mesh is given, its node axes enumerate all
                   N nodes, and ``cfg.topology.is_shift_structured()``.

RNG discipline (identical on both engines, which is what makes
dense-vs-sparse parity exact even for stochastic losses/compressors):
``state.rng`` is a fixed base key; round key = fold_in(rng, round_idx);
local step t key = fold_in(fold_in(round_key, 0), t); gossip step t key =
fold_in(fold_in(round_key, 1), t); per-node key = fold_in(step_key, node).

JAX API details are absorbed by ``repro.core.substrate``, never handled
here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import mixing as mixing_lib
from repro.core.compression import Compressor
from repro.core.substrate import (BatchedSubstrate, DenseSubstrate,
                                  NodeSubstrate, mesh_axis_size)
from repro.core.topology import Topology

PyTree = Any
LossFn = Callable[[PyTree, PyTree, jax.Array], jnp.ndarray]

__all__ = [
    "DFLConfig",
    "DFLState",
    "d_sgd_config",
    "c_sgd_config",
    "sync_sgd_config",
    "replicate",
    "average_model",
    "consensus_distance",
    "init_state",
    "local_phase",
    "gossip_phase",
    "round_keys",
    "make_round_fn",
    "make_pipeline_fns",
    "resolve_engine",
    "pipeline_round_body",
    "pipeline_drain_body",
    "round_wire_bits",
]


@dataclasses.dataclass(frozen=True)
class DFLConfig:
    """Hyper-parameters of one DFL instance.

    tau1: computation frequency (local update steps per round).
    tau2: communication frequency (gossip steps per round).
    topology: gossip graph / confusion matrix C.
    mixing_impl: 'dense'       — X C per step (paper-faithful baseline);
                 'dense_power' — X C^{tau2} collapsed into one contraction
                                 (uncompressed DFL only; beyond-paper opt);
                 handled sparsely by the launcher when C is circulant.
    compression: None for plain DFL; a Compressor for C-DFL.
    gamma: CHOCO consensus step size (paper uses 1.0 in Fig. 10).
    """

    tau1: int
    tau2: int
    topology: Topology
    mixing_impl: str = "dense"
    compression: Optional[Compressor] = None
    gamma: float = 1.0
    # optional time-varying topologies: round k uses
    # topology_schedule[k % len] (beyond-paper extension; e.g. alternating
    # ring orientations or random matchings — the theory's zeta becomes the
    # schedule's joint spectral quantity).
    topology_schedule: Tuple[Topology, ...] = ()

    def __post_init__(self):
        assert self.tau1 >= 1 and self.tau2 >= 0
        if self.compression is not None and self.mixing_impl == "dense_power":
            raise ValueError(
                "C-DFL interleaves compression with every gossip step; "
                "dense_power mixing is only valid for uncompressed DFL"
            )

    @property
    def tau(self) -> int:
        return self.tau1 + self.tau2

    @property
    def is_compressed(self) -> bool:
        return self.compression is not None


def d_sgd_config(topology: Topology, **kw) -> DFLConfig:
    """D-SGD special case: (tau1, tau2) = (1, 1)  [paper Sec. III-C1]."""
    return DFLConfig(tau1=1, tau2=1, topology=topology, **kw)


def c_sgd_config(tau: int, topology: Topology, **kw) -> DFLConfig:
    """C-SGD special case: (tau1, tau2) = (tau, 1)  [paper Sec. III-C2]."""
    return DFLConfig(tau1=tau, tau2=1, topology=topology, **kw)


def sync_sgd_config(num_nodes: int, tau1: int = 1, **kw) -> DFLConfig:
    """Synchronous SGD benchmark: C = J (zeta = 0)  [paper Corollary 1/2]."""
    from repro.core.topology import fully_connected

    return DFLConfig(tau1=tau1, tau2=1, topology=fully_connected(num_nodes), **kw)


class DFLState(NamedTuple):
    """Stacked per-node training state."""

    params: PyTree        # every leaf [N, ...]
    opt_state: PyTree     # every leaf [N, ...] (optimizer slots per node)
    hat_params: PyTree    # CHOCO shared estimates Y (None for plain DFL)
    rng: jax.Array        # base PRNG key, folded per step/node
    round_idx: jnp.ndarray  # scalar int32


def replicate(tree: PyTree, n: int) -> PyTree:
    """Stack n identical copies along a new leading node axis (the paper
    initializes all nodes at the same point, Sec. VI-A)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree
    )


def average_model(params: PyTree) -> PyTree:
    """u_t = X_t 1/N (the paper's average model)."""
    return jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), params)


def consensus_distance(params: PyTree) -> jnp.ndarray:
    """||X (I - J)||_F^2 / N — the local-drift quantity of Lemma 1."""
    total = 0.0
    n = None
    for leaf in jax.tree_util.tree_leaves(params):
        n = leaf.shape[0]
        mean = jnp.mean(leaf, axis=0, keepdims=True)
        total = total + jnp.sum((leaf.astype(jnp.float32) - mean) ** 2)
    assert n is not None
    return total / n


def init_state(
    params: PyTree, n: int, opt, rng: jax.Array, stacked: bool = False,
    compressed: bool = False,
) -> DFLState:
    """Build the stacked state from single-model params (or pre-stacked).

    ``compressed=True`` allocates the CHOCO shared-estimate tree (Alg. 2
    l.1 initializes w_hat = 0); plain DFL carries None and pays no memory.
    """
    stacked_params = params if stacked else replicate(params, n)
    opt_state = jax.vmap(opt.init)(stacked_params)
    hat = (jax.tree_util.tree_map(jnp.zeros_like, stacked_params)
           if compressed else None)
    return DFLState(
        params=stacked_params,
        opt_state=opt_state,
        hat_params=hat,
        rng=rng,
        round_idx=jnp.zeros((), jnp.int32),
    )


def _local_updates(
    cfg: DFLConfig, loss_fn: LossFn, opt, sub: NodeSubstrate,
    params: PyTree, opt_state: PyTree, local_key: jax.Array, batches: PyTree,
    constrain, tau1=None, node_mask=None,
) -> Tuple[PyTree, PyTree, jnp.ndarray]:
    """tau1 per-node SGD steps (Alg. 1 l.4), engine-agnostic.

    Dense: batch leaves [tau1, N, ...], params [N, ...], sub.vmap = vmap.
    Sparse: batch leaves [tau1, ...] local, params local, sub.vmap = id.

    ``constrain`` re-asserts the stacked-parameter sharding on grads and
    updated params each step: without it GSPMD may resolve the scan carry /
    vmapped-grad shardings to replicated and all-gather entire stacked
    weight trees (observed: 200 GiB/device on phi3.5-moe).

    ``tau1``: optional TRACED int32 step count (the dynamic-tau executor
    path). The batch leading dim is then the compiled bound tau1_max
    (= cfg.tau1) and only the first tau1 slices are read — a
    ``fori_loop`` with a dynamic trip count, so re-planning tau1 never
    retraces. ``None`` keeps the static ``scan`` (bit-identical legacy
    path).

    ``node_mask``: optional traced 0/1 participation mask in the
    substrate's LOCAL view (``sub.node_mask_local``). The update loop runs
    unconditionally — participation gates which nodes KEEP their result
    (``sub.select_nodes``), so the compiled program is mask-independent
    and the all-ones round is a bitwise select of the plain one. The loss
    metric averages over active nodes only.
    """
    grad_one = jax.value_and_grad(loss_fn)
    params0, opt_state0 = params, opt_state

    def step(carry, inp):
        params, opt_state = carry
        batch_t, t = inp
        keys = sub.node_keys(jax.random.fold_in(local_key, t))
        losses, grads = sub.vmap(grad_one)(params, batch_t, keys)
        grads = constrain(grads)
        updates, opt_state = sub.vmap(opt.update)(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), params, updates)
        params = constrain(params)
        return (params, opt_state), losses

    def finish(params, opt_state, per_node_loss):
        if node_mask is None:
            return params, opt_state, sub.mean_over_nodes(per_node_loss)
        params = sub.select_nodes(node_mask, params, params0)
        opt_state = sub.select_nodes(node_mask, opt_state, opt_state0)
        return params, opt_state, sub.masked_mean_over_nodes(
            per_node_loss, node_mask)

    if tau1 is None:
        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), (batches, jnp.arange(cfg.tau1)))
        return finish(params, opt_state, jnp.mean(losses, axis=0))

    def batch_at(t):
        return jax.tree_util.tree_map(
            lambda b: jax.lax.dynamic_index_in_dim(b, t, keepdims=False),
            batches)

    # step 0 runs unconditionally (tau1 >= 1 by DFLConfig), which also
    # yields the per-node loss accumulator's shape/dtype; the summation
    # order (l_0 + l_1 + ...) matches the static path's axis-0 reduce, so
    # dynamic and static rounds stay bitwise identical.
    carry, loss_sum = step((params, opt_state),
                           (batch_at(jnp.zeros((), jnp.int32)),
                            jnp.zeros((), jnp.int32)))

    def body(t, acc):
        carry, loss_sum = acc
        carry, losses = step(carry, (batch_at(t), t))
        return carry, loss_sum + losses

    (params, opt_state), loss_sum = jax.lax.fori_loop(
        1, tau1, body, (carry, loss_sum))
    return finish(params, opt_state, loss_sum / tau1.astype(loss_sum.dtype))


def _communicate_plain(cfg: DFLConfig, sub: NodeSubstrate, params: PyTree,
                       round_idx=None, tau2=None, edge_mask=None) -> PyTree:
    """tau2 uncompressed gossip steps (optionally round-varying topology).

    ``tau2``: optional TRACED int32 gossip count (dynamic-tau executor); the
    ``fori_loop`` trip count is then a device scalar bounded by cfg.tau2
    (the compiled maximum), so schedule changes never retrace. ``None``
    keeps the static legacy path.

    ``edge_mask``: optional traced [E] 0/1 participation mask over
    ``cfg.topology.edges()`` — masked edges gossip identity and their
    weight renormalizes onto the endpoints' self loops (``sub.mix``).
    """
    if tau2 is None and cfg.tau2 == 0:
        return params
    t2 = cfg.tau2 if tau2 is None else tau2
    dense = isinstance(sub, DenseSubstrate)
    if cfg.topology_schedule:
        assert dense and cfg.mixing_impl == "dense", (
            "topology schedules use the dense engine's dense mixing")
        assert edge_mask is None, (
            "participation masks are indexed against cfg.topology.edges(); "
            "a round-varying topology schedule has no stable edge list")
        branches = [
            (lambda p, t=t: jax.lax.fori_loop(
                0, t2, lambda _, q: mixing_lib.mix_dense(q, t), p))
            for t in cfg.topology_schedule
        ]
        sel = (round_idx if round_idx is not None
               else jnp.zeros((), jnp.int32)) % len(branches)
        return jax.lax.switch(sel, branches, params)
    if cfg.mixing_impl == "dense_power":
        assert dense, "dense_power mixing is a dense-engine feature"
        assert tau2 is None, (
            "dense_power collapses tau2 into C^tau2 at trace time; dynamic "
            "taus need iterated mixing (mixing_impl='dense')")
        assert edge_mask is None, (
            "dense_power bakes C^tau2 in at trace time; masked gossip "
            "needs iterated mixing (mixing_impl='dense')")
        return mixing_lib.mix_dense_power(params, cfg.topology, cfg.tau2)
    if cfg.mixing_impl != "dense":
        raise ValueError(f"unknown mixing_impl {cfg.mixing_impl!r}")
    return jax.lax.fori_loop(
        0, t2, lambda _, p: sub.mix(p, edge_mask=edge_mask), params)


def _communicate_choco(
    cfg: DFLConfig, params: PyTree, hat: PyTree, rng: jax.Array,
    sub: Optional[NodeSubstrate] = None, tau2=None, edge_mask=None,
) -> Tuple[PyTree, PyTree]:
    """tau2 CHOCO-G compressed gossip steps (Alg. 2 lines 6-11), shared by
    both engines: Y is mixed by ``sub.mix`` (dense einsum / ppermute), then
    ``sub.choco_step`` runs the move + compress + estimate update — the
    unfused composition by default, or the single-pass fused kernel on the
    sharded substrate under ``use_kernels`` — with per-node keys
    fold_in(fold_in(rng, t), node) on either substrate.

    ``tau2``: optional TRACED int32 step count (dynamic-tau executor) —
    the same iteration body runs under a dynamic-trip-count ``fori_loop``
    instead of the static ``scan``, with identical per-step key folding.
    """
    comp = cfg.compression
    assert comp is not None
    sub = sub if sub is not None else DenseSubstrate(cfg.topology)

    def one_step(carry, t):
        x, y = carry
        mixed_y = sub.mix(y, edge_mask=edge_mask)
        keys = sub.node_keys(jax.random.fold_in(rng, t))
        return sub.choco_step(comp, x, y, mixed_y, cfg.gamma, keys)

    if tau2 is None:
        (params, hat), _ = jax.lax.scan(
            lambda c, t: (one_step(c, t), None), (params, hat),
            jnp.arange(cfg.tau2)
        )
        return params, hat
    params, hat = jax.lax.fori_loop(
        0, tau2, lambda t, c: one_step(c, t), (params, hat))
    return params, hat


def round_keys(rng: jax.Array, round_idx) -> Tuple[jax.Array, jax.Array]:
    """(local_key, comm_key) for one round — THE folding discipline; both
    engines must derive their keys from here (see module docstring)."""
    round_key = jax.random.fold_in(rng, round_idx)
    return jax.random.fold_in(round_key, 0), jax.random.fold_in(round_key, 1)


def local_phase(
    cfg: DFLConfig, loss_fn: LossFn, opt, sub: NodeSubstrate,
    params: PyTree, opt_state: PyTree, local_key: jax.Array, batches: PyTree,
    constrain=None, tau1=None, node_mask=None,
) -> Tuple[PyTree, PyTree, jnp.ndarray]:
    """Stage 1 of a round: tau1 local SGD steps (Alg. 1 l.4).

    Thin named wrapper over ``_local_updates`` so callers (the overlapped
    executor, tests' pure-Python references) can compose the two round
    stages explicitly. ``node_mask`` is the substrate-LOCAL participation
    view (``sub.node_mask_local``). Returns (params', opt_state',
    mean_loss).
    """
    constrain = constrain or (lambda t: t)
    return _local_updates(cfg, loss_fn, opt, sub, params, opt_state,
                          local_key, batches, constrain, tau1=tau1,
                          node_mask=node_mask)


def gossip_phase(
    cfg: DFLConfig, sub: NodeSubstrate, params: PyTree, hat: Optional[PyTree],
    comm_key: jax.Array, round_idx, constrain=None, tau2=None, edge_mask=None,
) -> Tuple[PyTree, Optional[PyTree]]:
    """Stage 2 of a round: tau2 gossip steps (Alg. 1 l.6 / Alg. 2 l.6-11).

    Plain DFL mixes ``params`` and re-asserts ``constrain``; C-DFL runs the
    CHOCO-G error-feedback iteration over (params, hat). Returns
    (params', hat') with hat' = None on the plain path. The exchange this
    stage issues belongs to round ``round_idx`` (topology-schedule branch
    selection and the comm-key derivation agree on that index).
    """
    constrain = constrain or (lambda t: t)
    if cfg.is_compressed:
        assert hat is not None, "C-DFL needs init_state(..., compressed=True)"
        params, hat = _communicate_choco(cfg, params, hat, comm_key, sub,
                                         tau2=tau2, edge_mask=edge_mask)
    else:
        params = _communicate_plain(cfg, sub, params, round_idx, tau2=tau2,
                                    edge_mask=edge_mask)
        params = constrain(params)
    return params, hat


def round_body(
    cfg: DFLConfig, loss_fn: LossFn, opt, sub: NodeSubstrate,
    params: PyTree, opt_state: PyTree, hat: Optional[PyTree],
    rng: jax.Array, round_idx, batches: PyTree, constrain=None,
    taus: Optional[Tuple[jax.Array, jax.Array]] = None,
    masks: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[PyTree, PyTree, Optional[PyTree], dict]:
    """One full DFL/C-DFL round on either substrate: the single shared
    implementation both engines execute.

    ``taus``: optional ``(tau1, tau2)`` TRACED int32 scalars — the
    dynamic-tau executor path. ``cfg.tau1``/``cfg.tau2`` then act as the
    compiled maxima (batch leading dim / loop bounds) and the scalars pick
    the step counts actually run, so an adaptive re-plan changes them
    without retracing. RNG folding and per-step arithmetic are identical to
    the static path (bit-for-bit, tested in tests/test_executor.py).

    ``masks``: optional ``(node_mask [N], edge_mask [E])`` TRACED 0/1 int32
    vectors (REPLICATED on the sparse engine) — the sporadic-participation
    path. Masked nodes skip their local updates (keep params/opt slots,
    still gossip); masked edges gossip identity with the lost weight
    renormalized onto both endpoints' self loops, so the effective W stays
    doubly stochastic. The compiled program is mask-independent (masks
    gate selects and accumulation weights, never control flow), and the
    all-ones round is bitwise the unmasked one (tests/test_faults.py).
    RNG folding is untouched by masks.
    """
    constrain = constrain or (lambda t: t)
    tau1, tau2 = taus if taus is not None else (None, None)
    if masks is not None:
        node_mask, edge_mask = masks
        mask_local = sub.node_mask_local(node_mask)
    else:
        mask_local = edge_mask = None
    local_key, comm_key = round_keys(rng, round_idx)
    params, opt_state, mean_loss = local_phase(
        cfg, loss_fn, opt, sub, params, opt_state, local_key, batches,
        constrain, tau1=tau1, node_mask=mask_local)
    params, hat = gossip_phase(cfg, sub, params, hat, comm_key, round_idx,
                               constrain, tau2=tau2, edge_mask=edge_mask)
    metrics = {
        "loss": mean_loss,
        "consensus_sq": sub.consensus_sq(params),
    }
    return params, opt_state, hat, metrics


def make_round_fn(
    cfg: DFLConfig, loss_fn: LossFn, opt, constrain=None, *,
    engine: str = "dense", mesh=None, node_axes: Sequence[str] = ("data",),
    use_kernels: bool = False, dynamic_taus: bool = False,
    participation: bool = False, population: Optional[int] = None,
) -> Callable[..., Tuple[DFLState, dict]]:
    """Build the jittable one-round function for either engine.

    round_fn(state, batches) -> (state', metrics); batches leaves
    [tau1, N, local_batch...]. ``constrain``: optional params-tree sharding
    re-assertion (see _local_updates). The sparse engine's node axes are
    shard_map-manual so the node-dim constraint is structural there, but
    its non-node (auto) axes run unconstrained: passing a ``constrain``
    to the sparse engine on a mesh with a >1-sized auto axis RAISES
    (``make_sharded_round_fn``) instead of silently dropping it — the
    scan-carry all-gather blowup documented in _local_updates would
    otherwise return the moment partial-auto meshes are enabled. On
    node-only meshes (every auto axis size 1) there is nothing to
    re-assert and the sparse engine accepts-and-ignores it.

    engine: "dense" (default; any topology), "sparse" (shard_map +
    ppermute; needs ``mesh`` whose ``node_axes`` enumerate all N nodes and
    a shift-structured topology), "batched" (node-batched virtual
    population — see below), or "auto" (batched when a ``population`` is
    given, else sparse when eligible, else dense).
    ``use_kernels`` routes the sparse hot path through the Pallas kernels.

    ``dynamic_taus``: the returned function is
    round_fn(state, batches, tau1, tau2) with DEVICE-SCALAR step counts;
    cfg.tau1/cfg.tau2 become the compiled maxima (batches carry
    [cfg.tau1, ...] leading dims, only the first tau1 slices are read).
    One compile covers every (tau1, tau2) <= the maxima — the
    recompile-free hot path behind ``repro.core.executor``.

    ``participation``: the returned function is
    round_fn(state, batches, tau1, tau2, node_mask, edge_mask) with traced
    0/1 int32 masks ([N] over nodes, [E] over ``topology.edges()``) — the
    sporadic round semantic of ``round_body(..., masks=...)``. Requires
    ``dynamic_taus`` (masks ride the same schedule-as-data path) and plain
    per-step mixing (no dense_power / topology_schedule).

    ``population``: the node-batched mega-scale path (engine="batched").
    State leaves are stacked ``[population, ...]`` while ``cfg.topology``
    is the C-node COHORT graph; the returned function is
    round_fn(state, batches, tau1, tau2, cohort_ids, node_mask, edge_mask)
    with a traced ``[C]`` int32 vector of global virtual-node ids plus the
    usual participation masks over the cohort topology. Each round gathers
    the cohort rows, runs the UNCHANGED shared ``round_body`` (per-node
    keys fold the global ids — ``BatchedSubstrate.node_keys``), and
    scatters back; non-cohort nodes are bitwise frozen. At full population
    with identity ids the round is bitwise the dense engine's
    (tests/test_batched_parity.py). Implies the ``participation``
    constraints (dynamic taus, per-step mixing, no topology schedule).
    """
    if dynamic_taus and cfg.mixing_impl == "dense_power":
        raise ValueError(
            "dynamic taus need iterated mixing: dense_power bakes C^tau2 in "
            "at trace time (use mixing_impl='dense')")
    if participation or population is not None:
        if not dynamic_taus:
            raise ValueError(
                "participation masks ride the dynamic schedule-as-data "
                "path; pass dynamic_taus=True")
        if cfg.topology_schedule:
            raise ValueError(
                "participation masks index cfg.topology.edges(); a "
                "round-varying topology schedule has no stable edge list")
    engine = resolve_engine(engine, cfg, mesh, node_axes, population)
    if engine == "batched":
        if population is None:
            raise ValueError(
                "engine='batched' needs population=V (the virtual node "
                "count the state leaves are stacked over)")
        # build-time validation (population >= cohort size) happens here,
        # not inside the trace.
        BatchedSubstrate(cfg.topology, population)

        def batched_round_fn(state: DFLState, batches: PyTree, tau1, tau2,
                             cohort_ids, node_mask, edge_mask):
            sub = BatchedSubstrate(cfg.topology, population,
                                   jnp.asarray(cohort_ids, jnp.int32))
            params = sub.gather_cohort(state.params)
            opt_state = sub.gather_cohort(state.opt_state)
            hat = (sub.gather_cohort(state.hat_params)
                   if state.hat_params is not None else None)
            params, opt_state, hat, metrics = round_body(
                cfg, loss_fn, opt, sub, params, opt_state, hat,
                state.rng, state.round_idx, batches, constrain,
                taus=(jnp.asarray(tau1, jnp.int32),
                      jnp.asarray(tau2, jnp.int32)),
                masks=(jnp.asarray(node_mask, jnp.int32),
                       jnp.asarray(edge_mask, jnp.int32)))
            state = state._replace(
                params=sub.scatter_cohort(state.params, params),
                opt_state=sub.scatter_cohort(state.opt_state, opt_state),
                hat_params=(sub.scatter_cohort(state.hat_params, hat)
                            if hat is not None else None),
                round_idx=state.round_idx + 1)
            return state, metrics

        return batched_round_fn
    if population is not None:
        raise ValueError(
            f"population= is a batched-engine parameter (got engine="
            f"{engine!r}); the {engine} engine's node count IS the "
            "topology's")
    if engine == "sparse":
        from repro.core.sharded import make_sharded_round_fn

        assert mesh is not None, "sparse engine needs a mesh"
        return make_sharded_round_fn(cfg, loss_fn, opt, mesh,
                                     node_axes=node_axes,
                                     use_kernels=use_kernels,
                                     dynamic_taus=dynamic_taus,
                                     participation=participation,
                                     constrain=constrain)
    if engine != "dense":
        raise ValueError(f"unknown engine {engine!r}")
    sub = DenseSubstrate(cfg.topology)

    def body(state: DFLState, batches: PyTree, taus, masks=None):
        params, opt_state, hat, metrics = round_body(
            cfg, loss_fn, opt, sub, state.params, state.opt_state,
            state.hat_params, state.rng, state.round_idx, batches, constrain,
            taus=taus, masks=masks)
        state = state._replace(
            params=params, opt_state=opt_state, hat_params=hat,
            round_idx=state.round_idx + 1)
        return state, metrics

    if participation:
        def round_fn(state: DFLState, batches: PyTree, tau1, tau2,
                     node_mask, edge_mask):
            return body(state, batches,
                        (jnp.asarray(tau1, jnp.int32),
                         jnp.asarray(tau2, jnp.int32)),
                        masks=(jnp.asarray(node_mask, jnp.int32),
                               jnp.asarray(edge_mask, jnp.int32)))
    elif dynamic_taus:
        def round_fn(state: DFLState, batches: PyTree, tau1, tau2):
            return body(state, batches,
                        (jnp.asarray(tau1, jnp.int32),
                         jnp.asarray(tau2, jnp.int32)))
    else:
        def round_fn(state: DFLState, batches: PyTree):
            return body(state, batches, None)

    return round_fn


def pipeline_round_body(
    cfg: DFLConfig, loss_fn: LossFn, opt, sub: NodeSubstrate,
    params: PyTree, opt_state: PyTree, hat: Optional[PyTree],
    rng: jax.Array, round_idx, buf: PyTree, have, tau1, prev_tau2,
    batches: PyTree, constrain=None, node_mask=None, prev_edge_mask=None,
) -> Tuple[PyTree, PyTree, Optional[PyTree], PyTree, dict]:
    """One OVERLAPPED round: round k's local phase plus the one-round-stale
    fold of round k-1's gossip exchange (``overlap="pipeline"``).

    Dataflow (k = the round at ``round_idx``)::

        z_k = local_phase(p_k, batches_k)            # round k's tau1 steps
        g   = gossip_phase(buf = z_{k-1}, ...)       # round k-1's exchange,
                                                     #   INDEPENDENT of z_k
        p_{k+1} = z_k + (g - z_{k-1})                # fold one round late

    Because ``g`` depends only on the carried buffer, the tau2 ppermute
    exchange of round k-1 and the tau1 local updates of round k are
    independent in the compiled dataflow — the scheduler may issue the
    collective before/under the compute (the overlap the planner's
    ``max(0, tau2*T_gossip - overlap_window)`` round-time model prices).
    The cost is one round of mixing staleness: the delayed-mixing regime
    priced by ``planner.bounds.stale_mixing_zeta``.

    The stale exchange uses round k-1's comm key, trip count
    (``prev_tau2``) and edge mask, so a pipelined run applies exactly the
    same gossip operators as the legacy run, each one round later.
    ``have`` is a traced 0/1 scalar: 0 on the first scan iteration, where
    the exchange still runs (collective matching / mask-independence) but
    its fold is discarded bitwise. CHOCO's shared estimates ride the gossip
    chain sequentially (hat is only ever advanced by exchanges), so they
    need no extra buffer — just the same discard on iteration 0.

    Returns (params', opt_state', hat', buf'=z_k, metrics). The loss
    metric is round k's; ``consensus_sq`` is measured on the folded params.
    """
    constrain = constrain or (lambda t: t)
    if node_mask is not None:
        mask_local = sub.node_mask_local(node_mask)
    else:
        mask_local = None
    local_key, _ = round_keys(rng, round_idx)
    _, stale_comm_key = round_keys(rng, round_idx - 1)
    z, opt_state, mean_loss = local_phase(
        cfg, loss_fn, opt, sub, params, opt_state, local_key, batches,
        constrain, tau1=tau1, node_mask=mask_local)
    g, hat_g = gossip_phase(cfg, sub, buf, hat, stale_comm_key,
                            round_idx - 1, constrain, tau2=prev_tau2,
                            edge_mask=prev_edge_mask)
    keep = have != 0
    params = jax.tree_util.tree_map(
        lambda zl, gl, bl: jnp.where(keep, (zl + (gl - bl)).astype(zl.dtype),
                                     zl), z, g, buf)
    params = constrain(params)
    if cfg.is_compressed:
        hat = jax.tree_util.tree_map(
            lambda a, b: jnp.where(keep, a, b), hat_g, hat)
    metrics = {
        "loss": mean_loss,
        "consensus_sq": sub.consensus_sq(params),
    }
    return params, opt_state, hat, z, metrics


def pipeline_drain_body(
    cfg: DFLConfig, sub: NodeSubstrate, params: PyTree, hat: Optional[PyTree],
    rng: jax.Array, round_idx, buf: PyTree, prev_tau2, constrain=None,
    prev_edge_mask=None,
) -> Tuple[PyTree, Optional[PyTree]]:
    """Retire the in-flight exchange after a pipelined scan.

    ``round_idx`` is the POST-scan counter, so the outstanding exchange
    belongs to round ``round_idx - 1`` (its comm key / trip count / edge
    mask). Runs in the same executable as the scan, so a dispatched
    superstep always returns fully-drained state: no gossip crosses a
    superstep / checkpoint / restore boundary.
    """
    constrain = constrain or (lambda t: t)
    _, stale_comm_key = round_keys(rng, round_idx - 1)
    g, hat = gossip_phase(cfg, sub, buf, hat, stale_comm_key, round_idx - 1,
                          constrain, tau2=prev_tau2,
                          edge_mask=prev_edge_mask)
    params = jax.tree_util.tree_map(
        lambda pl, gl, bl: (pl + (gl - bl)).astype(pl.dtype), params, g, buf)
    params = constrain(params)
    return params, hat


def make_pipeline_fns(
    cfg: DFLConfig, loss_fn: LossFn, opt, constrain=None, *,
    engine: str = "dense", mesh=None, node_axes: Sequence[str] = ("data",),
    use_kernels: bool = False, participation: bool = False,
) -> Tuple[Callable[..., Tuple[DFLState, PyTree, dict]],
           Callable[..., DFLState]]:
    """Build the jittable pipelined-round pair for either engine
    (``overlap="pipeline"``; the executor scans ``pipe_fn`` and calls
    ``drain_fn`` once after the scan — see
    ``core.executor.make_pipeline_superstep``).

    Signatures (all step counts / flags are traced int32)::

        pipe_fn(state, buf, have, prev_tau2, batches, tau1)
            -> (state', buf', metrics)                       # plain
        pipe_fn(state, buf, have, prev_tau2, prev_edge_mask,
                batches, tau1, node_mask)
            -> (state', buf', metrics)                       # participation
        drain_fn(state, buf, prev_tau2[, prev_edge_mask]) -> state'

    The CURRENT round's (tau2, edge_mask) never enter ``pipe_fn``: that
    exchange is issued one scan iteration later from the carry (the whole
    point of the pipeline). The pipeline is dynamic-only — cfg.tau1 /
    cfg.tau2 are the compiled maxima exactly as in the dynamic round path.
    """
    if cfg.mixing_impl == "dense_power":
        raise ValueError(
            "overlap='pipeline' is dynamic-only: dense_power bakes C^tau2 "
            "in at trace time (use mixing_impl='dense')")
    if engine == "batched":
        raise ValueError(
            "overlap='pipeline' is not supported on the batched engine: "
            "consecutive rounds gossip over DIFFERENT sampled cohorts, so "
            "the in-flight exchange has no stable buffer to double-buffer "
            "(use overlap='none')")
    if participation and cfg.topology_schedule:
        raise ValueError(
            "participation masks index cfg.topology.edges(); a "
            "round-varying topology schedule has no stable edge list")
    engine = resolve_engine(engine, cfg, mesh, node_axes)
    if engine == "sparse":
        from repro.core.sharded import make_sharded_pipeline_fns

        assert mesh is not None, "sparse engine needs a mesh"
        return make_sharded_pipeline_fns(cfg, loss_fn, opt, mesh,
                                         node_axes=node_axes,
                                         use_kernels=use_kernels,
                                         participation=participation,
                                         constrain=constrain)
    if engine != "dense":
        raise ValueError(f"unknown engine {engine!r}")
    sub = DenseSubstrate(cfg.topology)

    def pipe_body(state: DFLState, buf, have, prev_tau2, batches, tau1,
                  node_mask=None, prev_edge_mask=None):
        params, opt_state, hat, z, metrics = pipeline_round_body(
            cfg, loss_fn, opt, sub, state.params, state.opt_state,
            state.hat_params, state.rng, state.round_idx, buf, have, tau1,
            prev_tau2, batches, constrain, node_mask=node_mask,
            prev_edge_mask=prev_edge_mask)
        state = state._replace(
            params=params, opt_state=opt_state, hat_params=hat,
            round_idx=state.round_idx + 1)
        return state, z, metrics

    def drain_body(state: DFLState, buf, prev_tau2, prev_edge_mask=None):
        params, hat = pipeline_drain_body(
            cfg, sub, state.params, state.hat_params, state.rng,
            state.round_idx, buf, prev_tau2, constrain,
            prev_edge_mask=prev_edge_mask)
        return state._replace(params=params, hat_params=hat)

    if participation:
        def pipe_fn(state, buf, have, prev_tau2, prev_edge_mask, batches,
                    tau1, node_mask):
            return pipe_body(state, buf, jnp.asarray(have, jnp.int32),
                             jnp.asarray(prev_tau2, jnp.int32), batches,
                             jnp.asarray(tau1, jnp.int32),
                             node_mask=jnp.asarray(node_mask, jnp.int32),
                             prev_edge_mask=jnp.asarray(prev_edge_mask,
                                                        jnp.int32))

        def drain_fn(state, buf, prev_tau2, prev_edge_mask):
            return drain_body(state, buf, jnp.asarray(prev_tau2, jnp.int32),
                              prev_edge_mask=jnp.asarray(prev_edge_mask,
                                                         jnp.int32))
    else:
        def pipe_fn(state, buf, have, prev_tau2, batches, tau1):
            return pipe_body(state, buf, jnp.asarray(have, jnp.int32),
                             jnp.asarray(prev_tau2, jnp.int32), batches,
                             jnp.asarray(tau1, jnp.int32))

        def drain_fn(state, buf, prev_tau2):
            return drain_body(state, buf, jnp.asarray(prev_tau2, jnp.int32))

    return pipe_fn, drain_fn


def resolve_engine(engine: str, cfg: DFLConfig, mesh,
                   node_axes: Sequence[str],
                   population: Optional[int] = None) -> str:
    """The engine that ``engine`` names: "auto" becomes "batched" when a
    ``population`` is given (it exceeds what any mesh enumerates: nodes
    are data, not hardware — docs/ARCHITECTURE.md engine rules), else
    "sparse" when ``sparse_engine_eligible``, else "dense"; any other
    name is returned as it is."""
    if engine != "auto":
        return engine
    if population is not None:
        return "batched"
    return ("sparse" if sparse_engine_eligible(cfg, mesh, node_axes)
            else "dense")


def sparse_engine_eligible(cfg: DFLConfig, mesh,
                           node_axes: Sequence[str]) -> bool:
    """True when the sparse (shard_map + ppermute) engine can run this
    config on this mesh: circulant topology, no dense-only features, and
    the node mesh axes enumerate exactly the N > 1 nodes."""
    if mesh is None or cfg.topology_schedule or cfg.mixing_impl != "dense":
        return False
    if not cfg.topology.is_shift_structured():
        return False
    n = cfg.topology.num_nodes
    if n <= 1:
        return False
    try:
        mesh_n = mesh_axis_size(mesh, tuple(node_axes))
    except KeyError:
        return False
    if mesh_n != n:
        return False
    # Non-node mesh axes stay auto (GSPMD) inside the sparse engine's
    # shard_map; on JAX pins whose partial-manual mode is broken, only
    # size-1 auto axes are safe (see substrate.supports_partial_auto).
    from repro.core import substrate as substrate_lib

    other = [a for a in mesh.axis_names if a not in node_axes]
    if any(mesh.shape[a] > 1 for a in other):
        return substrate_lib.supports_partial_auto()
    return True


def round_wire_bits(cfg: DFLConfig, params_one_node: PyTree,
                    engine: str = "sparse") -> float:
    """Analytic wire bits per node per ROUND (tau2 gossip steps).

    Uncompressed: each gossip step ships the full fp32 model per received
    copy; compressed: Q's bits_per_value. The copy count comes from
    ``mixing.gossip_copies_per_step(topology, engine)``: engine="sparse"
    (default) charges per-neighbor traffic — the paper's deployment
    accounting and the ppermute engine's actual cost — while "dense"
    charges the dense all-gather lowering's N-1 copies. Used by the
    Fig.-10-style wall-clock-per-bit benchmarks.
    """
    from repro.core.compression import Identity, tree_wire_bits

    comp = cfg.compression if cfg.is_compressed else Identity()
    copies = mixing_lib.gossip_copies_per_step(cfg.topology, engine)
    per_step = tree_wire_bits(comp, params_one_node) * copies
    return per_step * cfg.tau2
