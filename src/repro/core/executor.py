"""Recompile-free fused round executor: the DFL hot-loop dispatcher.

The paper's balancing result only pays off if *changing* the (tau1, tau2)
schedule is cheap; resource-constrained DFL work (Yan & Li 2023) wants it
re-planned per round. Before this module, every adaptive re-plan rebuilt and
re-jitted the round function (tau1/tau2 were static scan lengths), so the
controller had to discard compile-contaminated rounds. The executor makes
schedule changes and round dispatch near-zero-cost:

* **Schedule as data** — the schedule is a first-class ``[K, 2]`` int32
  device array, not control flow: the K-round superstep scans
  ``(tau1[k], tau2[k])`` as ``lax.scan`` xs alongside the batches, so every
  round of one dispatch can run a DIFFERENT (tau1, tau2)
  (``dispatch_trajectory``; the per-round adaptation of Yan & Li
  arXiv:2308.06496 and the sporadic schedules of DSpodFL arXiv:2402.03448).
  A uniform schedule is just the constant trajectory — ``dispatch(state,
  batches, tau1, tau2)`` broadcasts the pair to [K, 2] and shares the SAME
  compiled executable, so trajectories add zero compiles over PR-3's
  scalar path. Per round, ``round_body`` runs bounded loops over the
  (tau1_max, tau2_max) maxima with dynamic trip counts
  (``make_round_fn(..., dynamic_taus=True)``); any schedule within the
  maxima dispatches against the same executable and a re-plan never
  retraces (asserted via the trace counter below).
* **Fused supersteps** — a jitted ``lax.scan`` over K rounds with the
  ``DFLState`` carry DONATED (params+opt buffers reused in place, halving
  peak state memory vs. the undonated per-round jit) and on-device stacked
  metrics, so the host syncs once per superstep instead of once per round.
  Metrics come back tagged with the REALIZED schedule (``tau1``/``tau2``
  [K] rows), so downstream accounting never has to reconstruct which
  schedule a round actually ran.
* **Overlap** — ``HostPrefetcher`` builds the next superstep's batches on a
  background thread while the device runs, and ``MetricsBuffer`` defers the
  host-blocking metric fetch to log boundaries.
* **Telemetry** — every class here takes an optional ``telemetry=`` sink
  (``repro.obs.Telemetry``) and emits typed events: ``compile`` when a
  superstep traces, ``superstep`` per dispatch, ``prefetch`` build/cancel
  spans from the worker thread, ``flush`` when the buffer syncs. All hooks
  are host-side Python around the jitted calls — they add ZERO ops to the
  round-path HLO and ZERO host syncs (the ``telemetry-neutrality`` audit
  in ``repro.analysis`` proves the instrumented lowering is
  fingerprint-identical to the bare one).
* **Profiler spans** — with or without a sink, the same points open a
  ``jax.profiler.TraceAnnotation`` (``dfl.dispatch``, ``dfl.compile``,
  ``dfl.prefetch.build``, ``dfl.prefetch.take``, ``dfl.metrics.flush``),
  so a profiler trace shows what the host was doing on the device
  trace's clock; inside the superstep the engine's ``jax.named_scope``
  phases (``dfl.local``, ``dfl.local.update``, ``dfl.gossip``) name the
  device ops.

A keyed compile cache (``dynamic=False``) remains as the static fallback for
configs the dynamic path can't express (``mixing_impl='dense_power'``).

Numerics: a dynamic-tau round is bit-identical to the static round in model
state (params / opt_state / hat_params / consensus metric); the scalar loss
METRIC may differ by ~1 ulp because XLA associates the tau1-length and
tau1_max-length loss reductions differently (tests/test_executor.py pins
both properties).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dfl import (DFLConfig, DFLState, make_pipeline_fns,
                            make_round_fn, resolve_engine)
from repro.core.sharded import node_state_sharding

PyTree = Any

__all__ = ["RoundExecutor", "HostPrefetcher", "MetricsBuffer",
           "make_pipeline_superstep", "stack_round_batches"]


def stack_round_batches(round_batches: Sequence[PyTree],
                        tau1_max: int) -> PyTree:
    """Stack K per-round batch trees (leaves [tau1, ...]) into superstep
    form (leaves [K, tau1_max, ...]), zero-padding rows >= tau1.

    The padding rows are never read by the dynamic-trip-count loops — they
    only exist so every dispatch shares one compiled shape.
    """
    assert round_batches, "need at least one round of batches"

    def one(*leaves):
        leaves = [np.asarray(x) for x in leaves]
        k = len(leaves)
        tail = leaves[0].shape[1:]
        out = np.zeros((k, tau1_max) + tail, leaves[0].dtype)
        for i, x in enumerate(leaves):
            assert x.shape[0] <= tau1_max, (
                f"round batch has {x.shape[0]} steps > tau1_max={tau1_max}")
            out[i, :x.shape[0]] = x
        return jnp.asarray(out)

    return jax.tree_util.tree_map(one, *round_batches)


def make_pipeline_superstep(pipe_fn, drain_fn, *, participation: bool = False,
                            num_nodes: int = 0, num_edges: int = 0,
                            on_trace: Optional[Callable[[], None]] = None):
    """Fused K-round scan for ``overlap="pipeline"``.

    The scan carry is ``(state, buf, have, prev_tau2[, prev_edge_mask])``:
    ``buf`` holds the previous round's post-local params (the payload of
    the in-flight gossip exchange), ``have`` is 0 only on the first
    iteration (whose exchange runs but folds to a bitwise no-op), and the
    previous row's tau2/edge-mask ride the carry so round k's exchange
    executes — one iteration late — with round k's schedule data. After
    the scan, ``drain_fn`` retires the final in-flight exchange INSIDE the
    same executable, so a dispatched superstep always returns fully-drained
    state (checkpoint/restore never sees gossip in flight).

    ``superstep(state, batches, taus)`` matches the legacy dynamic
    superstep's signature/row layout exactly, so ``dispatch_trajectory``
    and the audits drive both modes identically. ``on_trace`` fires once
    per XLA trace (the executor's compile counter hook).
    """
    def superstep(state: DFLState, batches: PyTree, taus):
        if on_trace is not None:
            on_trace()
        n, e = num_nodes, num_edges
        buf0 = state.params
        have0 = jnp.zeros((), jnp.int32)
        pt2_0 = jnp.zeros((), jnp.int32)
        live = jnp.ones((), jnp.int32)

        if participation:
            def body(carry, xs):
                st, buf, have, pt2, pem = carry
                b, tau = xs
                st, buf, metrics = pipe_fn(st, buf, have, pt2, pem, b,
                                           tau[0], tau[2:2 + n])
                metrics = dict(
                    metrics,
                    active_nodes=jnp.sum(tau[2:2 + n]),
                    masked_edges=jnp.int32(e) - jnp.sum(tau[2 + n:]),
                    tau1=tau[0], tau2=tau[1])
                return (st, buf, live, tau[1], tau[2 + n:]), metrics

            pem0 = jnp.ones((e,), jnp.int32)
            carry0 = (state, buf0, have0, pt2_0, pem0)
            (st, buf, _, pt2, pem), metrics = jax.lax.scan(
                body, carry0, (batches, taus))
            st = drain_fn(st, buf, pt2, pem)
        else:
            def body(carry, xs):
                st, buf, have, pt2 = carry
                b, tau = xs
                st, buf, metrics = pipe_fn(st, buf, have, pt2, b, tau[0])
                return (st, buf, live, tau[1]), dict(
                    metrics, tau1=tau[0], tau2=tau[1])

            carry0 = (state, buf0, have0, pt2_0)
            (st, buf, _, pt2), metrics = jax.lax.scan(
                body, carry0, (batches, taus))
            st = drain_fn(st, buf, pt2)
        return st, metrics

    return superstep


class RoundExecutor:
    """Compile-once dispatch of DFL rounds and K-round supersteps.

    Args:
      cfg: the DFL config whose ``tau1``/``tau2`` are the compiled MAXIMA in
        dynamic mode (any dispatched schedule must satisfy
        1 <= tau1 <= cfg.tau1, 0 <= tau2 <= cfg.tau2) and defaults in static
        mode.
      loss_fn, opt, constrain, engine, mesh, node_axes, use_kernels:
        forwarded to ``core.dfl.make_round_fn``; ``engine="auto"`` is
        resolved once here (``core.dfl.resolve_engine``) and the result
        kept as ``self.engine``.
      dynamic: True (default) compiles the dynamic-tau round once; False is
        the keyed static fallback — one compile per distinct (tau1, tau2),
        cached.
      participation: widen the schedule rows to ``[K, 2 + N + E]`` — per
        round, (tau1, tau2) followed by an [N] 0/1 node-participation mask
        and an [E] 0/1 edge mask over ``cfg.topology.edges()`` — and run
        the sporadic round semantic (``round_body(..., masks=...)``).
        Plain [K, 2] trajectories are auto-padded with all-ones masks (and
        stay bitwise the unmasked rounds). Dynamic mode only: masks are
        schedule DATA scanned as xs, so heterogeneous participation shares
        the one compiled superstep (zero recompiles, audited).
      donate: donate the DFLState argument of every dispatch (the caller
        must treat the passed-in state as consumed).
      overlap: ``"none"`` (default) keeps the legacy superstep — the code
        path is untouched, so it is BITWISE the pre-overlap executor
        (asserted in tests/test_overlap.py). ``"pipeline"`` double-buffers
        the scan: round k's tau2 gossip exchange is issued alongside round
        k+1's tau1 local updates and folded one round late (one-round-stale
        mixing; see ``core.dfl.pipeline_round_body``), with the final
        exchange drained inside the same executable so dispatch boundaries
        never hold gossip in flight. Dynamic mode only. The planner prices
        the mode via ``CostModel(overlap=...)`` and
        ``bounds.stale_mixing_zeta``.
      telemetry: optional ``repro.obs.Telemetry`` sink; dispatches emit
        ``superstep`` events and traces emit ``compile`` events on the
        "dispatch" track. Host-side only — never traced into the HLO.

    ``dispatch(state, batches, tau1, tau2)`` runs one superstep: batches
    leaves are [K, tau1_max, ...] (dynamic) / [K, tau1, ...]-compatible
    (static mode slices the padded rows off), K inferred from the leading
    dim; returns ``(state', metrics)`` with metrics leaves stacked [K]
    (including the realized ``tau1``/``tau2`` per round).
    ``dispatch_trajectory(state, batches, taus)`` is the general form:
    ``taus`` is a [K, 2] int32 array and round k runs
    (taus[k, 0], taus[k, 1]) — scanned as xs through the SAME executable
    the uniform dispatch uses, so heterogeneous schedules cost zero extra
    compiles. ``compile_count`` counts traces of the superstep — the
    zero-recompile assertion hook for tests and benchmarks.
    """

    def __init__(
        self,
        cfg: DFLConfig,
        loss_fn,
        opt,
        *,
        constrain=None,
        engine: str = "dense",
        mesh=None,
        node_axes: Sequence[str] = ("data",),
        use_kernels: bool = False,
        dynamic: bool = True,
        participation: bool = False,
        donate: bool = True,
        telemetry=None,
        overlap: str = "none",
        population: Optional[int] = None,
    ):
        self.cfg = cfg
        self.dynamic = dynamic
        self.donate = donate
        self.num_nodes = cfg.topology.num_nodes
        self.num_edges = cfg.topology.num_edges
        engine = resolve_engine(engine, cfg, mesh, node_axes, population)
        self.engine = engine
        self.batched = engine == "batched"
        if self.batched:
            if population is None:
                raise ValueError(
                    "engine='batched' needs population=V (virtual node "
                    "count the state leaves are stacked over)")
            if not dynamic:
                raise ValueError(
                    "cohort ids are schedule data on the dynamic path; "
                    "the static fallback keys compiles on (tau1, tau2) "
                    "and cannot express per-round cohorts")
            # cohort rows subsume the participation layout (ids + masks).
            participation = True
        elif population is not None:
            raise ValueError(
                f"population= is a batched-engine parameter (got engine="
                f"{engine!r})")
        self.population = population
        self.participation = participation
        if overlap not in ("none", "pipeline"):
            raise ValueError(
                f"unknown overlap mode {overlap!r} (use 'none'|'pipeline')")
        if overlap == "pipeline" and not dynamic:
            raise ValueError(
                "overlap='pipeline' rides the dynamic superstep scan; the "
                "static fallback has no carry to double-buffer "
                "(pass dynamic=True)")
        if overlap == "pipeline" and self.batched:
            raise ValueError(
                "overlap='pipeline' is not supported on the batched "
                "engine: consecutive rounds gossip over DIFFERENT sampled "
                "cohorts (use overlap='none')")
        self.overlap = overlap
        if participation and not dynamic:
            raise ValueError(
                "participation masks are schedule data on the dynamic "
                "path; the static fallback keys compiles on (tau1, tau2) "
                "and cannot express per-round masks")
        # the sparse engine's state lives split over the node mesh axes;
        # ``place`` puts every dispatched state there, so a state built on
        # one device and one a dispatch returned trace the same superstep.
        self.state_sharding = (node_state_sharding(cfg, mesh, node_axes)
                               if engine == "sparse" else None)
        self._make_kw = dict(
            constrain=constrain, engine=engine, mesh=mesh,
            node_axes=tuple(node_axes), use_kernels=use_kernels)
        self._loss_fn = loss_fn
        self._opt = opt
        self._tel = telemetry
        self._trace_count = 0
        self.dispatch_count = 0
        self.rounds_dispatched = 0
        self._static_cache: Dict[Tuple[int, int], Callable] = {}
        # host-work memo for the dispatch hot path: validated/padded
        # trajectory rows + their device array, keyed on the raw bytes
        # (the adaptive controller re-emits unchanged chunks often, and
        # uniform dispatches always hit after warmup).
        self._traj_cache: Dict[Any, Tuple[np.ndarray, Any]] = {}
        if dynamic and overlap == "pipeline":
            pipe_fn, drain_fn = make_pipeline_fns(
                cfg, loss_fn, opt, participation=participation,
                **self._make_kw)

            def _traced():
                self._trace_count += 1  # fires per trace == per compile
                self._note_trace("pipeline")

            superstep = make_pipeline_superstep(
                pipe_fn, drain_fn, participation=participation,
                num_nodes=self.num_nodes, num_edges=self.num_edges,
                on_trace=_traced)
            self._dynamic_fn = jax.jit(
                superstep, donate_argnums=(0,) if donate else ())
        elif dynamic:
            round_fn = make_round_fn(cfg, loss_fn, opt, dynamic_taus=True,
                                     participation=(participation
                                                    and not self.batched),
                                     population=population,
                                     **self._make_kw)
            n, e = self.num_nodes, self.num_edges
            batched = self.batched

            def superstep(state: DFLState, batches: PyTree, taus):
                self._trace_count += 1  # fires per trace == per compile
                self._note_trace("dynamic")

                def body(st, xs):
                    b, tau = xs
                    if batched:
                        # cohort row layout: (tau1, tau2, ids [C],
                        # node mask [C], edge mask [E]) — ids and masks
                        # are schedule DATA, so every cohort draw rides
                        # the one compiled superstep (cohort-recompile
                        # audit).
                        nm = tau[2 + n:2 + 2 * n]
                        st, metrics = round_fn(
                            st, b, tau[0], tau[1], tau[2:2 + n],
                            nm, tau[2 + 2 * n:])
                        metrics = dict(
                            metrics,
                            active_nodes=jnp.sum(nm),
                            masked_edges=(jnp.int32(e)
                                          - jnp.sum(tau[2 + 2 * n:])))
                    elif participation:
                        st, metrics = round_fn(
                            st, b, tau[0], tau[1],
                            tau[2:2 + n], tau[2 + n:])
                        # realized participation alongside the realized
                        # schedule: what each round ACTUALLY ran.
                        metrics = dict(
                            metrics,
                            active_nodes=jnp.sum(tau[2:2 + n]),
                            masked_edges=(jnp.int32(e)
                                          - jnp.sum(tau[2 + n:])))
                    else:
                        st, metrics = round_fn(st, b, tau[0], tau[1])
                    # tag metrics with the REALIZED schedule so per-round
                    # accounting survives heterogeneous trajectories.
                    return st, dict(metrics, tau1=tau[0], tau2=tau[1])

                return jax.lax.scan(body, state, (batches, taus))

            self._dynamic_fn = jax.jit(
                superstep, donate_argnums=(0,) if donate else ())

    # -- telemetry ---------------------------------------------------------

    def _note_trace(self, kind: str) -> None:
        """Record one XLA trace of a superstep. Runs at TRACE time on the
        host (a Python side-effect of the traced closure, like the counter
        itself) — it inserts nothing into the jaxpr, so the lowered HLO is
        identical with or without a sink (audited)."""
        if self._tel is not None:
            self._tel.emit("compile", track="dispatch",
                           name=f"superstep-trace-{kind}",
                           count=self._trace_count)

    # -- properties --------------------------------------------------------

    @property
    def tau1_max(self) -> int:
        return self.cfg.tau1

    @property
    def tau2_max(self) -> int:
        return self.cfg.tau2

    @property
    def compile_count(self) -> int:
        """Number of XLA compilations this executor has triggered (a jit
        cache hit does not retrace, so a steady count across re-plans IS the
        recompile-free property)."""
        return self._trace_count

    # -- audit hook --------------------------------------------------------

    def lower_superstep(self, state: DFLState, batches: PyTree, taus):
        """Lower (without compiling) the dynamic superstep at example
        arguments — the compiled-artifact audit hook
        (``repro.analysis.audits``): donation is read off the compiled
        module's ``input_output_alias`` header, recompile hazards by
        fingerprinting lowerings at different trajectory values,
        collective matching off the optimized HLO's permute pairs.
        Audit lowerings do not touch ``compile_count`` (the
        zero-recompile assertions only count *dispatch* traces). A
        ``telemetry`` sink stays LIVE through the lowering on purpose:
        the ``telemetry-neutrality`` audit compares instrumented vs bare
        lowerings, so the instrumented trace must actually run its hooks.
        Dynamic mode only — the static fallback intentionally keys
        compiles on (tau1, tau2)."""
        if not self.dynamic:
            raise ValueError(
                "lower_superstep needs dynamic=True: the static fallback "
                "bakes (tau1, tau2) per compile by design")
        n = self._trace_count
        try:
            return self._dynamic_fn.lower(
                state, batches, jnp.asarray(taus, jnp.int32))
        finally:
            self._trace_count = n

    # -- dispatch ----------------------------------------------------------

    def _check_taus(self, tau1: int, tau2: int) -> Tuple[int, int]:
        tau1, tau2 = int(tau1), int(tau2)
        if not 1 <= tau1 <= self.tau1_max:
            raise ValueError(
                f"tau1={tau1} outside compiled bounds [1, {self.tau1_max}]; "
                "rebuild the executor with a larger tau1_max")
        if not 0 <= tau2 <= self.tau2_max:
            raise ValueError(
                f"tau2={tau2} outside compiled bounds [0, {self.tau2_max}]; "
                "rebuild the executor with a larger tau2_max")
        return tau1, tau2

    @property
    def row_width(self) -> int:
        """Trajectory row width: 2; 2 + N + E with participation; or
        2 + 2C + E on the batched engine (tau1, tau2, cohort ids [C],
        node mask [C], edge mask [E])."""
        if self.batched:
            return 2 + 2 * self.num_nodes + self.num_edges
        if self.participation:
            return 2 + self.num_nodes + self.num_edges
        return 2

    def _check_trajectory(self, taus, k: int) -> np.ndarray:
        arr = np.asarray(taus, dtype=np.int32)
        if self.batched:
            c = self.num_nodes
            if arr.ndim != 2 or arr.shape[1] not in (2, self.row_width):
                raise ValueError(
                    f"cohort trajectory must be [K, 2] (identity cohort, "
                    f"all-active) or [K, {self.row_width}] (tau1, tau2, "
                    f"cohort ids [{c}], node mask [{c}], edge mask "
                    f"[{self.num_edges}]) rows, got shape {arr.shape}")
            if arr.shape[1] == 2:  # plain schedule: identity cohort
                kk = arr.shape[0]
                arr = np.concatenate(
                    [arr,
                     np.broadcast_to(np.arange(c, dtype=np.int32), (kk, c)),
                     np.ones((kk, self.row_width - 2 - c), np.int32)],
                    axis=1)
            ids = arr[:, 2:2 + c]
            if ids.size:
                if ids.min() < 0 or ids.max() >= self.population:
                    raise ValueError(
                        f"cohort ids must lie in [0, {self.population}) "
                        f"(got range [{ids.min()}, {ids.max()}])")
                if any(len(np.unique(row)) != c for row in ids):
                    raise ValueError(
                        "cohort ids must be unique within each row "
                        "(a node cannot occupy two cohort slots)")
            masks = arr[:, 2 + c:]
            if masks.size and not np.isin(masks, (0, 1)).all():
                raise ValueError(
                    "participation masks must be 0/1 "
                    f"(got values {sorted(set(masks.ravel().tolist()))})")
        elif self.participation:
            if arr.ndim != 2 or arr.shape[1] not in (2, self.row_width):
                raise ValueError(
                    f"participation trajectory must be [K, 2] (all-active) "
                    f"or [K, {self.row_width}] (tau1, tau2, node mask "
                    f"[{self.num_nodes}], edge mask [{self.num_edges}]) "
                    f"rows, got shape {arr.shape}")
            if arr.shape[1] == 2:  # plain schedule: everyone participates
                arr = np.concatenate(
                    [arr, np.ones((arr.shape[0], self.row_width - 2),
                                  np.int32)], axis=1)
            masks = arr[:, 2:]
            if masks.size and not np.isin(masks, (0, 1)).all():
                raise ValueError(
                    "participation masks must be 0/1 "
                    f"(got values {sorted(set(masks.ravel().tolist()))})")
        elif arr.ndim != 2 or arr.shape[1] != 2:
            raise ValueError(
                f"trajectory must be [K, 2] (tau1, tau2) rows, got shape "
                f"{arr.shape}")
        if arr.shape[0] != k:
            raise ValueError(
                f"trajectory has {arr.shape[0]} rows but batches carry "
                f"K={k} rounds")
        for t1 in (int(arr[:, 0].min()), int(arr[:, 0].max())):
            if not 1 <= t1 <= self.tau1_max:
                raise ValueError(
                    f"tau1={t1} outside compiled bounds [1, {self.tau1_max}]"
                    "; rebuild the executor with a larger tau1_max")
        for t2 in (int(arr[:, 1].min()), int(arr[:, 1].max())):
            if not 0 <= t2 <= self.tau2_max:
                raise ValueError(
                    f"tau2={t2} outside compiled bounds [0, {self.tau2_max}]"
                    "; rebuild the executor with a larger tau2_max")
        return arr

    def _static_fn(self, tau1: int, tau2: int) -> Callable:
        key = (tau1, tau2)
        fn = self._static_cache.get(key)
        if fn is None:
            import dataclasses

            cfg = dataclasses.replace(self.cfg, tau1=tau1, tau2=tau2)
            round_fn = make_round_fn(cfg, self._loss_fn, self._opt,
                                     **self._make_kw)

            def superstep(state: DFLState, batches: PyTree):
                self._trace_count += 1
                self._note_trace("static")

                return jax.lax.scan(round_fn, state, batches)

            fn = jax.jit(superstep,
                         donate_argnums=(0,) if self.donate else ())
            self._static_cache[key] = fn
        return fn

    _TRAJ_CACHE_MAX = 128

    def _prepare_trajectory(self, key, build) -> Tuple[np.ndarray, Any]:
        """Memoized validation + padding + device transfer of a trajectory.

        ``_check_trajectory``'s numpy validation, the participation-mode
        all-ones mask padding, and the host->device ``jnp.asarray`` upload
        together dominate the CPU dispatch floor on micro models (ROADMAP:
        superstep K=1 was ~20% slower than a static jit). The adaptive
        controller re-emits unchanged chunks often and uniform dispatches
        repeat (k, tau1, tau2) forever, so both are keyed here — content
        bytes for explicit trajectories, the scalar triple for uniform
        ones — and repeated identical dispatches skip the host work
        entirely. Bounded FIFO so pathological schedule churn can't grow
        host memory."""
        hit = self._traj_cache.get(key)
        if hit is None:
            arr = build()
            # never alias caller memory: the cache key is content bytes,
            # so an in-place caller mutation must not retro-edit the entry.
            arr = arr.copy()
            dev = jnp.asarray(arr) if self.dynamic else None
            if len(self._traj_cache) >= self._TRAJ_CACHE_MAX:
                self._traj_cache.pop(next(iter(self._traj_cache)))
            self._traj_cache[key] = hit = (arr, dev)
        return hit

    def dispatch_trajectory(self, state: DFLState, batches: PyTree,
                            taus) -> Tuple[DFLState, dict]:
        """One fused superstep executing a heterogeneous schedule: round k
        runs (taus[k, 0], taus[k, 1]) local/gossip steps. ``taus`` is a
        [K, 2] int-like array with every row inside the compiled
        (tau1_max, tau2_max) bounds; batches leaves are [K, tau1_max, ...]
        (only the first taus[k, 0] rows of round k are read). In dynamic
        mode the trajectory rides the SAME executable as the uniform
        ``dispatch`` — schedule heterogeneity never compiles. The static
        fallback splits the trajectory into contiguous uniform segments and
        plays them through the keyed compile cache (one compile per
        distinct (tau1, tau2), as always). Returned metrics are stacked [K]
        and tagged with the realized per-round ``tau1``/``tau2``.

        Validation and the schedule's device upload are memoized on the
        trajectory's content (``_prepare_trajectory``), so re-dispatching
        an unchanged chunk costs no host-side re-checking."""
        k = jax.tree_util.tree_leaves(batches)[0].shape[0]
        raw = np.asarray(taus, dtype=np.int32)
        arr, dev = self._prepare_trajectory(
            (k, raw.shape, raw.tobytes()),
            lambda: self._check_trajectory(raw, k))
        return self._dispatch_prepared(state, batches, arr, dev, k)

    def place(self, state: DFLState) -> DFLState:
        """``state`` on this executor's engine layout (see
        ``state_sharding``); a no-op for a state already there and on the
        dense and batched engines. Every dispatch calls it, since a caller
        may pass a state built anywhere; for a placed state ``device_put``
        returns the same arrays, and the walk cost 0.92 ms median for the
        29-leaf qwen3-1.7b state on 4 CPU devices — beside supersteps of
        seconds at that model's published widths."""
        if self.state_sharding is None:
            return state
        return jax.device_put(state, self.state_sharding)

    def _dispatch_prepared(self, state: DFLState, batches: PyTree,
                           arr: np.ndarray, dev, k: int):
        with jax.profiler.TraceAnnotation("dfl.dispatch"):
            state = self.place(state)
            self.dispatch_count += 1
            self.rounds_dispatched += k
            if self._tel is None:
                return self._run_trajectory(state, batches, arr, dev, k)
            t0 = self._tel.now()
            out = self._run_trajectory(state, batches, arr, dev, k)
            # On sync backends (this jaxlib's CPU client) the superstep
            # EXECUTES inside the call, so dur is real device time; on
            # async backends (the TPU) it is enqueue cost and the flush
            # event carries the rest.
            dur = self._tel.now() - t0
        self._tel.emit("superstep", track="dispatch", name=f"superstep-k{k}",
                       t=t0, dur=dur, k=k, dispatch=self.dispatch_count)
        if self.overlap == "pipeline":
            # the gossip slice riding under the compute slice: the stale
            # exchange of rounds [0, k) is in flight INSIDE this dispatch
            # window (drained before it returns), so the overlap track
            # mirrors the superstep span one level down.
            self._tel.emit("overlap", track="overlap",
                           name=f"gossip-inflight-k{k}", t=t0, dur=dur,
                           mode=self.overlap, k=k,
                           dispatch=self.dispatch_count)
        return out

    def _run_trajectory(self, state: DFLState, batches: PyTree,
                        arr: np.ndarray, dev, k: int
                        ) -> Tuple[DFLState, dict]:
        if self.dynamic:
            return self._dynamic_fn(
                state, batches, dev if dev is not None else jnp.asarray(arr))
        # static fallback: contiguous uniform segments, padding rows
        # (which the dynamic layout carries) sliced off per segment.
        parts: List[dict] = []
        i = 0
        while i < k:
            j = i + 1
            while j < k and (arr[j] == arr[i]).all():
                j += 1
            t1, t2 = int(arr[i, 0]), int(arr[i, 1])
            seg = jax.tree_util.tree_map(lambda b: b[i:j, :t1], batches)
            state, m = self._static_fn(t1, t2)(state, seg)
            parts.append(dict(
                m,
                tau1=jnp.full((j - i,), t1, jnp.int32),
                tau2=jnp.full((j - i,), t2, jnp.int32)))
            i = j
        metrics = {key: (parts[0][key] if len(parts) == 1
                         else jnp.concatenate([p[key] for p in parts]))
                   for key in parts[0]}
        return state, metrics

    def dispatch(self, state: DFLState, batches: PyTree, tau1: int,
                 tau2: int) -> Tuple[DFLState, dict]:
        """One K-round fused superstep (K = batches' leading dim) at a
        uniform (tau1, tau2): the constant-trajectory special case. The
        broadcast [K, 2] schedule (plus its validation and device upload)
        is memoized on (k, tau1, tau2) — the steady-state uniform dispatch
        does no per-call host schedule work at all."""
        tau1, tau2 = self._check_taus(tau1, tau2)
        k = jax.tree_util.tree_leaves(batches)[0].shape[0]
        arr, dev = self._prepare_trajectory(
            ("uniform", k, tau1, tau2),
            lambda: self._check_trajectory(
                # repro-lint: disable=no-host-coercion-of-device-scalars (dispatch's taus are host ints by API contract — _check_taus already coerced them; this builds the broadcast schedule, it reads no device value)
                np.tile(np.array([[tau1, tau2]], np.int32), (k, 1)), k))
        return self._dispatch_prepared(state, batches, arr, dev, k)

    def dispatch_round(self, state: DFLState, batches: PyTree, tau1: int,
                       tau2: int) -> Tuple[DFLState, dict]:
        """Single-round convenience: batches leaves [tau1_max, ...];
        returns per-round (unstacked) metrics."""
        add_k = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        state, metrics = self.dispatch(state, add_k(batches), tau1, tau2)
        return state, jax.tree_util.tree_map(lambda m: m[0], metrics)

    def warmup(self, state: DFLState, batches: PyTree,
               tau1: int = 1, tau2: int = 0) -> None:
        """Pay the trace+compile for this batch SHAPE (and, in static mode,
        this (tau1, tau2) key) before any measured dispatch, so no measured
        round contains a compile. Compiles ahead of time from the
        arguments' shapes and shardings — nothing runs and no copy of
        ``state`` is made, so warming a model that fills the device costs
        no device memory. Dynamic mode compiles one executable per shape;
        static mode must warm every (tau1, tau2) it will dispatch.
        Dispatch statistics are left untouched."""
        def spec(x):
            # single-device and host arrays lower for the default device,
            # which is where both fresh and dispatch-returned ones hit the
            # cache.
            sharding = getattr(x, "sharding", None)
            if sharding is not None and len(sharding.device_set) == 1:
                sharding = None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        k = jax.tree_util.tree_leaves(batches)[0].shape[0]
        if self.dynamic:
            fn = self._dynamic_fn
            args = (batches, np.zeros((k, self.row_width), np.int32))
        else:
            tau1, tau2 = self._check_taus(tau1, tau2)
            fn = self._static_fn(tau1, tau2)
            args = (jax.tree_util.tree_map(lambda b: b[:, :tau1], batches),)
        args = jax.tree_util.tree_map(spec, (self.place(state),) + args)
        with jax.profiler.TraceAnnotation("dfl.compile"), (
                self._tel.span("warmup", track="dispatch")
                if self._tel is not None else contextlib.nullcontext()):
            fn.lower(*args).compile()


class HostPrefetcher:
    """Double-buffered host batch prefetch.

    ``schedule(fn, *args, meta=...)`` starts building the NEXT superstep's
    batches on a daemon thread while the device executes the current one;
    ``take()`` joins and returns ``(result, meta)``. The ``meta`` tag (e.g.
    ``(round0, k, tau1)``) lets the caller detect a stale prefetch after a
    re-plan changed the schedule and rebuild inline — re-plans are rare, so
    at most one chunk is ever discarded.

    Failure paths are hard errors, not asserts (they survive ``-O``):
    double-``schedule`` and ``take`` without a schedule raise
    ``RuntimeError``; a worker exception is re-raised on ``take``.

    ``retries``: transient batch-build ``Exception``s are retried on the
    worker thread up to ``retries`` extra attempts with exponential
    backoff (``backoff_s``, doubling per attempt) before the LAST error is
    parked for ``take()`` to re-raise — a flaky data source degrades a
    prefetch to slower instead of killing the run. Non-``Exception``
    ``BaseException``s (KeyboardInterrupt, SystemExit) are never retried.
    ``close()`` is the clean-shutdown path: it stops any backoff wait,
    joins the pending worker (no thread leak on teardown), and drops its
    result/error; the prefetcher refuses new ``schedule`` calls after.

    ``stats`` counts scheduled/taken/cancelled/stale/errors/retries; with
    a ``telemetry`` sink the WORKER thread emits a ``prefetch`` build span
    (so host batch construction shows as its own track in the timeline)
    and cancels/stales/retries emit instants.
    """

    def __init__(self, telemetry=None, retries: int = 0,
                 backoff_s: float = 0.05):
        assert retries >= 0 and backoff_s >= 0.0
        self._pending: Optional[Tuple[threading.Thread, dict, Any]] = None
        self._tel = telemetry
        self._retries = int(retries)
        self._backoff_s = float(backoff_s)
        self._stop = threading.Event()
        self.stats: Dict[str, int] = {
            "scheduled": 0, "taken": 0, "cancelled": 0, "stale": 0,
            "errors": 0, "retries": 0}

    def schedule(self, fn: Callable, *args, meta: Any = None) -> None:
        if self._stop.is_set():
            raise RuntimeError("prefetcher closed — no further schedules")
        if self._pending is not None:
            raise RuntimeError(
                "previous prefetch not taken — call take() or cancel() "
                "before scheduling another build")
        self.stats["scheduled"] += 1
        box: dict = {}
        tel = self._tel

        def work():
            t0 = tel.now() if tel is not None else 0.0
            try:
                with jax.profiler.TraceAnnotation("dfl.prefetch.build"):
                    self._build(fn, args, box)
            finally:
                if tel is not None:
                    tel.emit("prefetch", track="prefetch", name="build",
                             t=t0, dur=tel.now() - t0, action="build",
                             ok="err" not in box)

        t = threading.Thread(target=work, daemon=True)
        t.start()
        self._pending = (t, box, meta)

    def _build(self, fn: Callable, args: tuple, box: dict) -> None:
        """The worker's build with its retries: ``box`` gets ``out`` or
        the last ``err``."""
        for attempt in range(self._retries + 1):
            try:
                box["out"] = fn(*args)
                box.pop("err", None)
                return
            except BaseException as e:  # re-raised on take()
                box["err"] = e
                if attempt >= self._retries or not isinstance(e, Exception):
                    return
                self.stats["retries"] += 1
                if self._tel is not None:
                    self._tel.emit("prefetch", track="prefetch",
                                   name="retry", action="retry",
                                   attempt=attempt + 1)
                # interruptible backoff: close() wakes it
                if self._stop.wait(self._backoff_s * (2 ** attempt)):
                    return

    @property
    def pending_meta(self) -> Any:
        return self._pending[2] if self._pending is not None else None

    def take(self) -> Tuple[Any, Any]:
        if self._pending is None:
            raise RuntimeError("nothing scheduled — call schedule() first")
        t, box, meta = self._pending
        self._pending = None
        with jax.profiler.TraceAnnotation("dfl.prefetch.take"):
            t.join()
        if "err" in box:
            self.stats["errors"] += 1
            raise box["err"]
        self.stats["taken"] += 1
        return box["out"], meta

    def cancel(self) -> None:
        """Discard a stale prefetch (joins the worker; a build error in
        data that will never be used is dropped, not re-raised)."""
        if self._pending is None:
            return
        t, box, _meta = self._pending
        self._pending = None
        t.join()
        self.stats["cancelled"] += 1
        if self._tel is not None:
            self._tel.emit("prefetch", track="prefetch", name="cancel",
                           action="cancel")

    def mark_stale(self) -> None:
        """Caller-noted stale take: the prefetched chunk was rebuilt
        because a re-plan changed the schedule after it was scheduled
        (counts toward the hit/stale attribution in run reports)."""
        self.stats["stale"] += 1
        if self._tel is not None:
            self._tel.emit("prefetch", track="prefetch", name="stale",
                           action="stale")

    def close(self) -> None:
        """Clean shutdown: wake any backoff wait, join the pending worker
        thread, and discard its result or parked error. Idempotent; the
        prefetcher rejects ``schedule`` afterwards. Call on every exit
        path (success, exception, signal teardown) so a failed build can
        never leak its thread past the run."""
        already = self._stop.is_set()
        self._stop.set()
        if self._pending is not None:
            t, _box, _meta = self._pending
            self._pending = None
            t.join()
            self.stats["cancelled"] += 1
        if self._tel is not None and not already:
            self._tel.emit("prefetch", track="prefetch", name="close",
                           action="close")


class MetricsBuffer:
    """On-device stacked round metrics, host-materialized only on flush.

    ``push`` records a dispatched superstep's device metrics WITHOUT
    blocking; ``flush`` calls ``jax.block_until_ready`` once (at a log /
    checkpoint / re-plan boundary), converts to per-round host rows, and
    amortizes the measured wall-clock since the window opened over the
    rounds it covered (per-round dispatch would instead pay one sync per
    round).

    ``dispatched_at``: pass ``time.perf_counter()`` taken BEFORE the
    dispatch call. On synchronous backends (this jaxlib's CPU client) the
    superstep EXECUTES inside ``dispatch``, so a window opened at push time
    would measure ~zero; the pre-dispatch stamp of the window's first chunk
    is the correct wall-clock origin on sync and async backends both. It
    also means a compile occurring inside a dispatch lands in that window —
    warm every batch shape up front (see ``launch.train``) so measured
    rounds stay compile-free.

    All window arithmetic is on the MONOTONIC ``perf_counter`` clock: a
    wall-clock jump (NTP step, DST) must never corrupt ``round_s``, which
    feeds the ``AdaptiveController`` least-squares cost fit. Absolute
    timestamps exist only in telemetry ``run`` headers.

    With a ``telemetry`` sink, ``flush`` emits a ``flush`` event spanning
    the host-blocking ``block_until_ready`` (the metrics track shows
    exactly when — and for how long — the host actually synced).
    """

    def __init__(self, telemetry=None):
        self._pending: List[Tuple[int, int, int, int, dict]] = []
        self._window_start: Optional[float] = None
        self._tel = telemetry

    def push(self, round0: int, k: int, tau1: Optional[int],
             tau2: Optional[int], metrics: dict,
             dispatched_at: Optional[float] = None) -> None:
        """``tau1``/``tau2`` may be None when the metrics carry the
        realized per-round ``tau1``/``tau2`` rows (executor dispatches tag
        them); metric-carried values win over the scalars either way, so
        heterogeneous-trajectory supersteps report the schedule each round
        actually ran."""
        if self._window_start is None:
            self._window_start = (dispatched_at if dispatched_at is not None
                                  else time.perf_counter())
        self._pending.append((round0, k, tau1, tau2, metrics))

    @property
    def pending_rounds(self) -> int:
        return sum(k for _, k, _, _, _ in self._pending)

    def flush(self) -> List[dict]:
        """Block once; return one row per completed round, in order."""
        if not self._pending:
            return []
        block0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dfl.metrics.flush"):
            jax.block_until_ready([m for *_, m in self._pending])
        now = time.perf_counter()
        elapsed = now - (self._window_start or now)
        n = self.pending_rounds
        if self._tel is not None:
            block_s = now - block0
            self._tel.emit("flush", track="metrics", name="metrics-flush",
                           t=self._tel.now() - block_s, dur=block_s,
                           rounds=n, window_s=elapsed)
        per_round_s = elapsed / max(n, 1)
        rows: List[dict] = []
        int_cols = ("active_nodes", "masked_edges")
        for round0, k, tau1, tau2, metrics in self._pending:
            host = {key: np.asarray(v) for key, v in metrics.items()}
            tau1s = host.pop("tau1", None)
            tau2s = host.pop("tau2", None)
            for i in range(k):
                row = {key: (int(v[i]) if key in int_cols else float(v[i]))
                       for key, v in host.items()}
                row.update(
                    round=round0 + i,
                    tau1=int(tau1s[i]) if tau1s is not None else tau1,
                    tau2=int(tau2s[i]) if tau2s is not None else tau2,
                    round_s=per_round_s)
                rows.append(row)
        self._pending = []
        self._window_start = None
        return rows
