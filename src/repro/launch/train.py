"""DFL training CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --nodes 4 --tau1 4 --tau2 4 --rounds 20 --batch 4 --seq 128

``--reduced`` (the default) trains the arch's small smoke-test variant;
``--no-reduced`` trains its chip share (``ArchConfig.chip_share``): every
published width, with only the keys the config lists cut to what one chip
of the stated deployment holds. The run executes on the host's devices.

Engine selection (--engine): "auto" (default) runs the sparse shard_map +
ppermute engine when the host has exactly --nodes devices and the topology
is circulant (``sparse_engine_eligible``), else the dense stacked-array
engine; "sparse" forces it (errors if ineligible); "dense" forces the
reference path. --use-kernels routes the sparse hot path through the
Pallas kernels (interpret mode off-TPU).

Mega-scale (--virtual-nodes V [--cohort C]): the node-batched engine
stacks model state over V virtual nodes on one host and activates a
uniformly-sampled C-node cohort per round (C defaults to --nodes; the
gossip topology is built over the cohort). Cohort ids are schedule data
on the ``[K, 2+2C+E]`` trajectory rows, so every draw rides ONE compiled
executable (the ``cohort-recompile`` audit), data shards stream lazily by
global node id, and --faults compose (masks apply within the cohort).
Needs --dispatch fused; see benchmarks/bench_megascale.py for the
rounds/s / host-memory envelope up to 1M nodes.

Dispatch (--dispatch, --superstep): the hot loop runs on
``repro.core.executor.RoundExecutor``. "fused" (default) compiles ONE
dynamic-(tau1, tau2) round executable and dispatches --superstep rounds per
call as a donated-carry ``lax.scan`` — schedule changes never recompile,
and the host syncs with the device once per superstep (logging, checkpoints
and re-plans all happen at superstep boundaries). "static" is the legacy
keyed-compile-cache fallback: one compile per distinct (tau1, tau2).
Next-superstep batches are prefetched on a background thread while the
device runs.

Adaptive planning (--plan-budget SECONDS): hands (tau1, tau2) control to
``repro.planner.adaptive``. The controller plans the first schedule from a
neutral cost prior, measures real round wall-clock, re-fits per-step
compute/gossip times, and re-plans until the budget is spent; the schedule
trajectory lands in the history JSON (--history-out, ``schedule`` field =
the realized per-round [tau1, tau2] rows). With the fused executor a
re-plan is schedule DATA, so no round is ever compile-contaminated and
every measured round enters the controller's cost fit.

Schedule control (--schedule): "adaptive" (default with --plan-budget)
re-plans at superstep boundaries every --replan-every rounds;
"trajectory" re-plans INSIDE the superstep — each dispatch executes a
per-round [K, 2] (tau1, tau2) trajectory from
``AdaptiveController.next_trajectory`` via
``executor.dispatch_trajectory`` (probe rounds for identifiability ride
the last round of a chunk), still with zero recompiles. "fixed" pins the
CLI taus.

Telemetry (--telemetry-out events.jsonl): every run streams typed events
through ``repro.obs.Telemetry`` — rounds, supersteps, plan/replan/probe
decisions, compiles, prefetch builds, metric flushes, checkpoints, and
per-superstep counter snapshots (kernel op_stats deltas, compile count,
wire-bit totals, prefetch hit/stale). The --history-out JSON is a
schema-versioned VIEW over that stream (``repro.obs.history_view``) with
the same fields as before plus ``schema_version``. Inspect a stream with
``python -m repro.obs report|validate|trace export``. --profile-dir DIR
additionally wraps the run in ``jax.profiler`` so XLA device activity
can be lined up under the same timeline. All telemetry is host-side:
zero syncs, zero recompiles on the round path (audited —
``telemetry-neutrality`` in ``repro.analysis``).

All durations here are measured on the monotonic ``time.perf_counter``
clock (a wall-clock jump must never corrupt ``round_s`` or poison the
controller's least-squares fit); the only absolute timestamp is the
telemetry run header's ``wall_start``.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_arch, list_archs
from repro.core import (DFLConfig, HostPrefetcher, MetricsBuffer,
                        RoundExecutor, init_state, make_compressor, ring,
                        round_wire_bits, sparse_engine_eligible,
                        stack_round_batches, fully_connected,
                        paper_quasi_ring)
from repro.core.compression import Identity, tree_wire_bits
from repro.core.substrate import make_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.data.lm import (SyntheticLM, lm_batches_for_cohort,
                           lm_batches_for_dfl)
from repro.faults import CohortSampler, FaultPlan, load_fault_spec
from repro.kernels.ops import op_stats_delta
from repro.launch.steps import kernelize_compressor
from repro.models import train_loss, init_params
from repro.obs import Telemetry, history_view
from repro.optim import sgd, momentum_sgd, adamw
from repro.planner import AdaptiveController, Budget, unit_cost_model
from repro.planner.optimize import DEFAULT_GRID


def make_topology(name: str, n: int):
    return {
        "ring": lambda: ring(n),
        "full": lambda: fully_connected(n),
        "quasi": lambda: paper_quasi_ring(),
    }[name]()


def make_optimizer(name: str, lr: float):
    return {
        "sgd": lambda: sgd(lr),
        "momentum": lambda: momentum_sgd(lr),
        "adamw": lambda: adamw(lr),
    }[name]()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the arch's smoke-test variant; "
                         "--no-reduced trains its chip share at the "
                         "published widths")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--tau1", type=int, default=4)
    ap.add_argument("--tau2", type=int, default=4)
    ap.add_argument("--topology", default="ring",
                    choices=["ring", "full", "quasi"])
    ap.add_argument("--compression", default="",
                    choices=["", "top_k", "rand_k", "qsgd", "rand_gossip"])
    ap.add_argument("--gamma", type=float, default=0.6)
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "dense", "sparse"])
    ap.add_argument("--use-kernels", action="store_true",
                    help="Pallas kernels: sparse-engine gossip + fused "
                         "CHOCO compress-and-move, and the kernel-backed "
                         "TopK compressor on either engine (dispatch per "
                         "repro.kernels.registry; interpret mode off-TPU)")
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "momentum", "adamw"])
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4, help="per node")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--noniid", type=float, default=0.5)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--superstep", type=int, default=4,
                    help="rounds fused into one dispatch (K); logging / "
                         "checkpoint / re-plan granularity")
    ap.add_argument("--dispatch", default="fused",
                    choices=["fused", "static"],
                    help="fused: compile-once dynamic-tau executor; "
                         "static: legacy keyed per-(tau1,tau2) compile cache")
    ap.add_argument("--overlap", default="none",
                    choices=["none", "pipeline"],
                    help="superstep execution: 'pipeline' double-buffers "
                         "the scan so round k's gossip exchange overlaps "
                         "round k+1's local updates and folds in one round "
                         "late (one-round-stale mixing; the planner prices "
                         "both the hidden wire time and the staleness "
                         "penalty); 'none' is the paper-faithful "
                         "sequential round (bitwise the legacy path)")
    ap.add_argument("--plan-budget", type=float, default=0.0,
                    help="wall-clock budget (s); enables the adaptive "
                         "(tau1, tau2) planner (repro.planner.adaptive)")
    ap.add_argument("--replan-every", type=int, default=5,
                    help="rounds between re-plans when --plan-budget is set")
    ap.add_argument("--schedule", default="auto",
                    choices=["auto", "fixed", "adaptive", "trajectory"],
                    help="schedule control: fixed CLI taus, adaptive "
                         "boundary re-plans, or per-round [K, 2] "
                         "trajectories dispatched inside each superstep "
                         "(needs --plan-budget and --dispatch fused); "
                         "auto = adaptive iff --plan-budget is set")
    ap.add_argument("--virtual-nodes", type=int, default=0,
                    help="mega-scale mode: simulate this many virtual "
                         "nodes on one host via the node-batched engine — "
                         "model state is stacked [V, ...] and each round "
                         "activates a sampled --cohort over the --nodes "
                         "topology, with cohort ids as schedule data "
                         "(zero recompiles across draws; needs "
                         "--dispatch fused)")
    ap.add_argument("--cohort", type=int, default=0,
                    help="nodes sampled per round under --virtual-nodes "
                         "(default: --nodes, the cohort topology size)")
    ap.add_argument("--cohort-seed", type=int, default=0,
                    help="seed of the per-round cohort draw stream "
                         "(SeedSequence([seed, round]) — resume-safe)")
    ap.add_argument("--faults", default="",
                    help="deterministic fault injection: a JSON fault spec "
                         "(or @file.json) — see repro.faults. Rounds run "
                         "SPORADICALLY: crashed/masked nodes skip local "
                         "updates, dead edges gossip identity (mixing "
                         "renormalized), all with zero recompiles "
                         "(needs --dispatch fused)")
    ap.add_argument("--faults-seed", type=int, default=None,
                    help="override the fault spec's seed (the "
                         "SporadicParticipation Bernoulli stream)")
    ap.add_argument("--history-out", default="",
                    help="write the round/plan history JSON here (a "
                         "schema-versioned view over the telemetry stream)")
    ap.add_argument("--telemetry-out", default="",
                    help="append the full typed event stream here as JSONL "
                         "(inspect with `python -m repro.obs ...`)")
    ap.add_argument("--profile-dir", default="",
                    help="also capture a jax.profiler trace of the run "
                         "(XLA device activity) into this directory")
    args = ap.parse_args(argv)

    # The telemetry sink exists unconditionally (in-memory if no
    # --telemetry-out): the history JSON is derived from it either way.
    tel = Telemetry(path=args.telemetry_out or None, meta=dict(vars(args)))

    arch = get_arch(args.arch)
    if args.reduced:
        cfg = arch.reduced
    elif arch.chip_share is None:
        raise SystemExit(f"--no-reduced: {arch.arch_id} has no chip-share "
                         "config (ArchConfig.chip_share)")
    else:
        cfg = arch.chip_share.model
    enable_compile_cache()
    n = args.nodes
    population = args.virtual_nodes
    sampler = None
    if args.cohort and not population:
        raise SystemExit("--cohort samples a virtual population; set "
                         "--virtual-nodes V")
    if population:
        if args.dispatch != "fused":
            raise SystemExit("--virtual-nodes runs cohort ids as schedule "
                             "data through the dynamic executor; the "
                             "static keyed cache can't (use --dispatch "
                             "fused)")
        if args.engine != "auto":
            raise SystemExit("--virtual-nodes selects the node-batched "
                             "engine; leave --engine auto")
        if args.overlap == "pipeline":
            raise SystemExit("--overlap pipeline double-buffers a fixed "
                             "node set; sampled cohorts change every round "
                             "(use --overlap none)")
        # the gossip topology is built over the COHORT (n becomes the
        # per-round active set size); the population only sizes the
        # stacked state and the shard id space.
        n = args.cohort or args.nodes
        sampler = CohortSampler(population=population, cohort=n,
                                seed=args.cohort_seed)
        print(f"mega-scale: population={population} cohort={n} "
              f"(sampling rate {sampler.rate:.4f})")
    comp = kernelize_compressor(
        make_compressor(args.compression) if args.compression else None,
        args.use_kernels)
    topology = make_topology(args.topology, n)
    opt = make_optimizer(args.optimizer, args.lr)

    fault_plan = None
    if args.faults:
        if args.dispatch != "fused":
            raise SystemExit("--faults runs sporadic rounds through the "
                             "participation trajectory path; the static "
                             "keyed cache can't (use --dispatch fused)")
        spec = load_fault_spec(args.faults)
        if args.faults_seed is not None:
            spec["seed"] = args.faults_seed
        fault_plan = FaultPlan.from_spec(topology, spec)
        print(f"fault plan: {len(fault_plan.faults)} fault(s), "
              f"seed={fault_plan.seed}")

    # mega-scale: shards are keyed by GLOBAL virtual node id, built lazily
    # (a 1M-node corpus costs O(cohort) host memory, and shard content is
    # independent of construction/access order — prefetcher-thread safe).
    corpus = SyntheticLM(vocab_size=cfg.vocab_size, num_nodes=population or n,
                         noniid_alpha=args.noniid, lazy=bool(population))

    def loss_fn(p, b, k):
        return train_loss(p, b, cfg, k)

    # shapes only: the wire accounting and planner prior need no values.
    params0, _ = init_params(cfg, jax.random.key(0), abstract=True)

    mesh = None
    if not population and args.engine != "dense" and len(jax.devices()) == n:
        mesh = make_mesh((n,), ("nodes",))

    schedule_mode = args.schedule
    if schedule_mode == "auto":
        schedule_mode = "adaptive" if args.plan_budget > 0 else "fixed"
    if schedule_mode in ("adaptive", "trajectory") and args.plan_budget <= 0:
        raise SystemExit(f"--schedule {schedule_mode} needs --plan-budget")
    if schedule_mode == "trajectory" and args.dispatch != "fused":
        raise SystemExit("--schedule trajectory dispatches per-round "
                         "[K, 2] schedules through the dynamic executor; "
                         "the static keyed cache can't (use --dispatch "
                         "fused)")
    if args.overlap == "pipeline" and args.dispatch != "fused":
        raise SystemExit("--overlap pipeline rides the fused superstep "
                         "scan's double-buffered carry; the static keyed "
                         "cache has nothing to overlap (use --dispatch "
                         "fused)")

    # Adaptive planner: --plan-budget hands (tau1, tau2) control to
    # repro.planner.adaptive, which re-fits per-step compute/gossip times
    # from measured round wall-clock and re-plans every --replan-every
    # rounds (or emits per-round trajectories under --schedule
    # trajectory). The CLI taus seed the neutral prior's first schedule.
    controller = None
    tau1, tau2 = args.tau1, args.tau2
    if schedule_mode in ("adaptive", "trajectory"):
        model_bits = tree_wire_bits(Identity(), params0)
        # neutral prior: t_compute_step = t_gossip_step = 1 s, with the
        # real topology and model wire size (same accounting as planner).
        # The executor's overlap mode rides the prior so every (re)plan
        # prices the max-form round time AND the staleness penalty
        # (planner.cost / planner.bounds) — the fitted model preserves it
        # (dataclasses.replace).
        prior = unit_cost_model(topology, 1.0,
                                rep_dim=max(int(model_bits // 32), 1),
                                overlap=args.overlap)
        controller = AdaptiveController(
            Budget(wall_clock_s=args.plan_budget), prior,
            sigma=1.0, f_gap=1.0, replan_every=args.replan_every,
            compressors=(comp,), telemetry=tel)
        p = controller.initial_plan()
        tau1, tau2 = p.tau1, p.tau2
        print(f"planned tau=({tau1},{tau2}) for budget "
              f"{args.plan_budget:.1f}s (predicted bound "
              f"{p.predicted_bound:.4f})")

    # The executor compiles ONCE against the (tau1_max, tau2_max) bounds:
    # with a planner those are the schedule grid's maxima so any re-plan
    # dispatches against the same executable; without, the CLI taus.
    if controller is not None:
        tau1_max = max(max(t1 for t1, _ in DEFAULT_GRID), tau1)
        tau2_max = max(max(t2 for _, t2 in DEFAULT_GRID), tau2)
    else:
        tau1_max, tau2_max = tau1, tau2
    dcfg_max = DFLConfig(tau1=tau1_max, tau2=tau2_max, topology=topology,
                         compression=comp, gamma=args.gamma)
    eligible = (mesh is not None
                and sparse_engine_eligible(dcfg_max, mesh, ("nodes",)))
    if args.engine == "sparse" and not eligible:
        raise SystemExit(
            "sparse engine needs #devices == --nodes and a circulant "
            f"topology (devices={len(jax.devices())}, nodes={n}, "
            f"topology={topology.name})")
    executor = RoundExecutor(
        dcfg_max, loss_fn, opt, engine=args.engine, mesh=mesh,
        node_axes=("nodes",), use_kernels=args.use_kernels,
        dynamic=args.dispatch == "fused",
        participation=fault_plan is not None, telemetry=tel,
        overlap=args.overlap, population=population or None)
    engine = executor.engine

    def build_state():
        params, _ = init_params(cfg, jax.random.key(0))
        return init_state(params, population or n, opt, jax.random.key(1),
                          compressed=comp is not None)

    # one program builds the stacked state in the engine's layout, so a
    # model that fills the device is never held twice (a single-model
    # copy beside the stack, or the stack on one device of a ring).
    state = jax.jit(build_state, out_shardings=executor.state_sharding)()
    start_round = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        restored, start_round = restore_checkpoint(args.ckpt_dir, state.params)
        state = executor.place(state._replace(
            params=jax.tree_util.tree_map(jnp.asarray, restored)))
        print(f"restored round {start_round} from {args.ckpt_dir}")

    # Wire accounting is DEPLOYMENT cost (what a real DFL network ships:
    # engine="auto" = per-neighbor when circulant), not the host-simulation
    # engine's, so the printed MB/round is host-device-count independent
    # and comparable with benchmarks/common.py.
    import dataclasses as _dc

    wire_cache = {}

    def wire_bits_for(t1: int, t2: int) -> float:
        """Deployment wire bits for one (tau1, tau2) round (memoized —
        the schedule grid is tiny)."""
        key = (int(t1), int(t2))
        if key not in wire_cache:
            wire_cache[key] = round_wire_bits(
                _dc.replace(dcfg_max, tau1=key[0], tau2=key[1]),
                params0, engine="auto")
        return wire_cache[key]

    bits = wire_bits_for(tau1, tau2)
    print(f"arch={cfg.name} nodes={n} tau=({tau1},{tau2}) "
          f"zeta={topology.zeta:.3f} comp={args.compression or 'none'} "
          f"engine={engine} dispatch={args.dispatch} "
          f"overlap={args.overlap} schedule={schedule_mode} "
          f"superstep={args.superstep} wire={bits/8e6:.1f} MB/round/node")

    def round_batch(r: int, t1: int):
        """One round's [t1, N, B, ...] batch tree (same data stream the
        legacy per-round loop fetched). Mega-scale: cohort slot j streams
        the shard of the GLOBAL node ``sampler.draw(r)[j]`` — a pure
        function of (seed, node, step), so prefetch threading cannot
        reorder shards."""
        if sampler is not None:
            b = dict(lm_batches_for_cohort(corpus, t1, sampler.draw(r),
                                           args.batch, args.seq, r))
        else:
            b = dict(lm_batches_for_dfl(corpus, t1, n, args.batch,
                                        args.seq, r))
        if cfg.has_memory_input:
            m = cfg.memory_tokens or 16
            key = jax.random.key(1000 + r)
            b["memory"] = jax.random.normal(
                key, (t1, n, args.batch, m, cfg.memory_dim or cfg.d_model),
                jnp.float32)
        return b

    def build_batches(r0: int, k: int, t1: int):
        """[k, tau1_max, N, B, ...] superstep batches for rounds
        r0..r0+k-1 (rows >= t1 zero-padded, never read)."""
        return stack_round_batches([round_batch(r0 + i, t1)
                                    for i in range(k)], tau1_max)

    def dummy_batches(k: int):
        """Zeros in the superstep batch shape — executor warmup only."""
        zero = jax.tree_util.tree_map(jnp.zeros_like, round_batch(0, 1))
        return stack_round_batches([zero] * k, tau1_max)

    end = start_round + args.rounds

    def chunk_len(r: int, rounds_done: int) -> int:
        k = min(max(args.superstep, 1), end - r)
        if schedule_mode == "adaptive":
            # cut at re-plan boundaries so rounds_done % replan_every == 0
            # lands exactly at a superstep edge (trajectory mode re-plans
            # inside every superstep instead, so no cut there).
            to_replan = args.replan_every - rounds_done % args.replan_every
            k = min(k, to_replan)
        return k

    # Warm every superstep shape the run will dispatch (the chunk-length
    # sequence is deterministic in (rounds, superstep, replan boundaries))
    # with a throwaway dummy dispatch, so no MEASURED round ever contains a
    # trace/compile: that is what lets every observed round enter the
    # controller's cost fit. The static fallback compiles per (tau1, tau2)
    # key, so it re-warms after every re-plan (one dummy superstep of
    # compute instead of a contaminated measurement).
    def remaining_chunk_lens(rr: int, done: int):
        """Distinct superstep sizes the run will still dispatch from round
        rr (deterministic in (rounds, superstep, replan boundaries))."""
        ks = set()
        while rr < end:
            kk = chunk_len(rr, done)
            ks.add(kk)
            rr += kk
            done += kk
        return sorted(ks, reverse=True)

    warmed_shapes = set()   # superstep lengths K already compiled

    def warm_executables(ks, t1: int, t2: int) -> None:
        """Pre-pay compiles on dummy data so no MEASURED round contains
        one. Fused compiles per SHAPE only (the schedule args are
        irrelevant — one executable serves every (tau1, tau2)); static
        compiles per (shape, (tau1, tau2)) key. Warmup wall-clock is real
        budget spend and is charged to the controller, but never enters
        the per-round cost fit."""
        tw0 = time.perf_counter()
        before = executor.compile_count
        for kk in ks:
            if args.dispatch == "fused":
                executor.warmup(state, dummy_batches(kk))
            else:
                executor.warmup(state, dummy_batches(kk), t1, t2)
            warmed_shapes.add(kk)
        if executor.compile_count > before:
            print(f"warmed {executor.compile_count - before} superstep "
                  f"executable(s) in {time.perf_counter()-tw0:.1f}s")
        if controller is not None:
            controller.spend_overhead(time.perf_counter() - tw0)

    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
        print(f"jax profiler trace -> {args.profile_dir}")

    if args.rounds > 0:
        warm_executables(remaining_chunk_lens(start_round, 0), tau1, tau2)
    compiles_after_warmup = executor.compile_count

    buffer = MetricsBuffer(telemetry=tel)
    # transient host batch-build failures retry with backoff on the
    # worker thread; the close() in the finally below joins any pending
    # worker on EVERY exit path (no thread leak past the run).
    prefetch = HostPrefetcher(telemetry=tel, retries=2)
    t0 = time.perf_counter()
    rounds_done = 0
    wire_total = 0.0
    last_ckpt = start_round
    last_loss = float("nan")

    def emit_counters(round0: int, kk: int, opd) -> None:
        """Per-superstep counter attribution: kernel op_stats deltas from
        the enclosing dispatch, cumulative compile/wire/prefetch state."""
        tel.emit("counters", track="dispatch", name="superstep-counters",
                 round0=round0, k=kk,
                 compile_count=executor.compile_count,
                 wire_bits_total=wire_total,
                 prefetch_taken=prefetch.stats["taken"],
                 prefetch_stale=prefetch.stats["stale"],
                 prefetch_cancelled=prefetch.stats["cancelled"],
                 **{f"kernel_{key}": v for key, v in opd.as_dict().items()})

    def do_checkpoint(step: int, extra: dict) -> None:
        ck0 = tel.now()
        save_checkpoint(args.ckpt_dir, step, state.params, extra)
        tel.emit("checkpoint", track="checkpoint", name=f"ckpt-{step}",
                 t=ck0, dur=tel.now() - ck0, round=step)

    def flush_rows():
        """Materialize buffered metrics into round events/logs and feed
        the controller. Adaptive mode observes per round (uniform chunks,
        so the amortized round_s is exact); trajectory mode observes per
        CHUNK (heterogeneous schedules share one fused dispatch — only
        the chunk total is measurable, and ``observe_chunk``'s aggregated
        fit row keeps the least-squares fit exact). The history JSON is
        reconstructed from these events at the end (history_view)."""
        nonlocal last_loss, wire_total
        rows = buffer.flush()
        for row in rows:
            r = row["round"]
            wire_total += wire_bits_for(row["tau1"], row["tau2"])
            extra = {}
            if "active_nodes" in row:
                # sporadic run: realized participation rides every round
                # event (history/report attribute loss to availability).
                degraded = (row["active_nodes"] < n
                            or row["masked_edges"] > 0)
                extra = dict(active_nodes=row["active_nodes"],
                             masked_edges=row["masked_edges"],
                             degraded=degraded)
            if sampler is not None:
                # mega-scale: the history view's schema-4 cohort columns.
                extra.update(cohort_size=n, population=population)
            tel.emit("round", track="rounds", name=f"round-{r}",
                     round=r, tau1=row["tau1"], tau2=row["tau2"],
                     loss=row["loss"], consensus_sq=row["consensus_sq"],
                     round_s=row["round_s"],
                     wire_bits=wire_bits_for(row["tau1"], row["tau2"]),
                     **extra)
            if extra.get("degraded"):
                tel.emit("degraded", track="faults", name=f"degraded-{r}",
                         round=r, active_nodes=row["active_nodes"],
                         masked_edges=row["masked_edges"])
            if fault_plan is not None:
                for payload in fault_plan.events(r):
                    tel.emit("fault", track="faults",
                             name=f"{payload['kind']}-{payload['phase']}",
                             round=r, **payload)
                if controller is not None:
                    nm, em = fault_plan.masks(r)
                    controller.observe_participation(nm, em)
            last_loss = row["loss"]
            if (r + 1) % args.log_every == 0:
                done = r + 1 - start_round
                print(f"round {r+1:4d} tau=({row['tau1']},{row['tau2']}) "
                      f"loss={row['loss']:.4f} "
                      f"consensus={row['consensus_sq']:.3e} "
                      f"({(time.perf_counter()-t0)/max(done,1):.1f}s/round)",
                      flush=True)
            if controller is not None and schedule_mode != "trajectory":
                controller.observe(row["tau1"], row["tau2"], row["round_s"])
        if rows and controller is not None and schedule_mode == "trajectory":
            controller.observe_chunk(
                [(row["tau1"], row["tau2"]) for row in rows],
                sum(row["round_s"] for row in rows))

    try:
        if schedule_mode == "trajectory":
            # Per-round schedule control: every superstep dispatches a [k, 2]
            # trajectory planned by the controller — the re-plan happens
            # INSIDE the superstep (probe rounds included), not at its
            # boundary, and the realized per-round schedule comes back in the
            # metrics rows. Host batch build overlaps the device via the
            # prefetcher, keyed on the controller's PREDICTED next
            # trajectory (``predict_trajectory`` runs the exact planning
            # the next ``next_trajectory`` will commit, so after
            # ``flush_rows`` the prediction matches unless new overhead
            # spend shifted the budget — a mismatch rebuilds inline and
            # counts as a stale take).

            def build_traj_batches(r0: int, t1_rows):
                """[k, tau1_max, N, B, ...] batches for a [k]-row tau1
                column (batch content depends only on tau1, not tau2)."""
                return stack_round_batches(
                    [round_batch(r0 + i, int(t1))
                     for i, t1 in enumerate(t1_rows)], tau1_max)

            def tau1_key(r0: int, rows) -> tuple:
                return (r0, tuple(int(t1) for t1, *_rest in rows))

            def schedule_predicted(r0: int, done: int) -> bool:
                """Prefetch against the predicted next chunk; False when
                no further chunk is predicted (end / budget)."""
                if r0 >= end or controller.exhausted:
                    return False
                pred = controller.predict_trajectory(chunk_len(r0, done))
                if pred is None:
                    return False
                prefetch.schedule(build_traj_batches, r0, pred[:, 0],
                                  meta=tau1_key(r0, pred))
                return True

            r = start_round
            pending = schedule_predicted(r, rounds_done)
            while r < end:
                k = chunk_len(r, rounds_done)
                taus = controller.next_trajectory(k, round_idx=rounds_done)
                if taus is None:
                    print(f"budget exhausted after {rounds_done} rounds "
                          f"({controller.spent_s:.1f}s)")
                    break
                if len(taus) not in warmed_shapes:
                    # a superstep length the pre-loop warmup never saw (a
                    # budget-paced short chunk, or the shifted chunk grid
                    # after one): a new batch SHAPE — warm it on dummy data
                    # so the measured rounds stay compile-free.
                    tw0 = time.perf_counter()
                    executor.warmup(state, dummy_batches(len(taus)))
                    warmed_shapes.add(len(taus))
                    controller.spend_overhead(time.perf_counter() - tw0)
                # host batch build is real wall-clock the budget pays for —
                # charge the take-stall (or inline rebuild) as overhead,
                # not as round time.
                tb0 = time.perf_counter()
                batches = None
                if pending:
                    got, meta = prefetch.take()
                    if meta == tau1_key(r, taus):
                        batches = got
                    else:
                        prefetch.mark_stale()
                if batches is None:
                    span = "stale-rebuild" if pending else "batch-build"
                    with tel.span(span, track="prefetch"):
                        batches = build_traj_batches(r, taus[:, 0])
                controller.spend_overhead(time.perf_counter() - tb0)
                sched_rows = (fault_plan.mask_trajectory(taus, r)
                              if fault_plan is not None else taus)
                if sampler is not None:
                    # splice the per-round cohort draws in front of the
                    # (possibly fault-masked) participation columns.
                    sched_rows = sampler.cohort_trajectory(
                        sched_rows, r, num_edges=topology.num_edges)
                t_dispatch = time.perf_counter()
                with op_stats_delta() as opd:
                    state, metrics = executor.dispatch_trajectory(
                        state, batches, sched_rows)
                buffer.push(r, len(taus), None, None, metrics,
                            dispatched_at=t_dispatch)
                r += len(taus)
                rounds_done += len(taus)
                flush_rows()   # every realized round enters the cost fit
                # predict + schedule the NEXT chunk right after the flush:
                # the controller state now equals what the next
                # next_trajectory call will see, so the prediction is
                # deterministic-identical barring later overhead spend.
                pending = schedule_predicted(r, rounds_done)
                emit_counters(r - len(taus), len(taus), opd)
                if (args.ckpt_every and args.ckpt_dir
                        and r // args.ckpt_every
                        > last_ckpt // args.ckpt_every):
                    do_checkpoint(r, {"loss": last_loss})
                    last_ckpt = r

        # fixed/adaptive modes: the prefetched uniform-schedule superstep loop
        # (trajectory mode already ran above; r = end skips it).
        r = end if schedule_mode == "trajectory" else start_round
        k = chunk_len(r, rounds_done) if r < end else 0
        if k > 0:
            prefetch.schedule(build_batches, r, k, tau1, meta=(r, k, tau1))
        while r < end:
            batches, meta = prefetch.take()
            if meta != (r, k, tau1):   # stale after a re-plan changed tau1
                prefetch.mark_stale()
                with tel.span("stale-rebuild", track="prefetch"):
                    batches = build_batches(r, k, tau1)
            t_dispatch = time.perf_counter()  # sync backends EXECUTE inside
            with op_stats_delta() as opd:     # dispatch
                if fault_plan is not None or sampler is not None:
                    # widen the uniform chunk to masked participation /
                    # sampled cohort rows — same executable, the masks and
                    # cohort ids are just more xs columns.
                    rows = np.tile(np.array([[tau1, tau2]], np.int32),
                                   (k, 1))
                    if fault_plan is not None:
                        rows = fault_plan.mask_trajectory(rows, r)
                    if sampler is not None:
                        rows = sampler.cohort_trajectory(
                            rows, r, num_edges=topology.num_edges)
                    state, metrics = executor.dispatch_trajectory(
                        state, batches, rows)
                else:
                    state, metrics = executor.dispatch(state, batches, tau1,
                                                       tau2)
            buffer.push(r, k, tau1, tau2, metrics, dispatched_at=t_dispatch)
            emit_counters(r, k, opd)
            r += k
            rounds_done += k
            # overlap: build the NEXT superstep's batches while the device runs
            # this one (a later re-plan invalidates at most this one chunk).
            k_next = chunk_len(r, rounds_done)
            if k_next > 0:
                prefetch.schedule(build_batches, r, k_next, tau1,
                                  meta=(r, k_next, tau1))
            # host sync boundary: re-plans need per-round timings each chunk;
            # otherwise only log/checkpoint boundaries (or the end) block.
            boundary = (controller is not None
                        or any((rr + 1) % args.log_every == 0
                               for rr in range(r - k, r))
                        or (args.ckpt_every
                            and r // args.ckpt_every > last_ckpt // args.ckpt_every)
                        or r >= end)
            if boundary:
                flush_rows()
            if (args.ckpt_every and args.ckpt_dir
                    and r // args.ckpt_every > last_ckpt // args.ckpt_every):
                # superstep granularity: the checkpoint lands at the first
                # superstep edge at/after the --ckpt-every multiple.
                do_checkpoint(r, {"loss": last_loss})
                last_ckpt = r
            if controller is not None:
                new = controller.maybe_replan(rounds_done)
                if controller.exhausted:
                    print(f"budget exhausted after {rounds_done} rounds "
                          f"({controller.spent_s:.1f}s)")
                    break
                if new is not None:
                    tau1, tau2 = new.tau1, new.tau2
                    print(f"replanned tau=({tau1},{tau2}) at round {r} "
                          f"(t_step={new.round_cost.t_compute_step:.3f}s, "
                          f"t_gossip={new.round_cost.t_gossip_step:.3f}s, "
                          f"predicted bound {new.predicted_bound:.4f}, "
                          f"recompiles so far: {executor.compile_count})")
                    if args.dispatch == "static" and r < end:
                        # the static cache compiles per (tau1, tau2): pay the
                        # new key on dummy data now — for the chunk sizes
                        # still ahead only — not inside a measured round.
                        warm_executables(remaining_chunk_lens(r, rounds_done),
                                         tau1, tau2)
            k = chunk_len(r, rounds_done)
            flush_rows()
    finally:
        prefetch.close()
    if args.ckpt_dir:
        do_checkpoint(start_round + rounds_done, {})
    if args.profile_dir:
        jax.profiler.stop_trace()
    # run-level summary counters: the stream-derived history view reads
    # schedule_mode / compile counts from here, and reports read the
    # final wire/prefetch totals. compile_count must equal
    # compile_count_warmup under fused dispatch: every re-plan reused the
    # warmed executables.
    tel.emit("counters", track="run", name="run-summary",
             schedule_mode=schedule_mode,
             rounds_done=rounds_done,
             engine=engine,
             state_devices=len(
                 jax.tree_util.tree_leaves(state.params)[0].sharding
                 .device_set),
             compile_count_warmup=compiles_after_warmup,
             compile_count=executor.compile_count,
             wire_bits_total=wire_total,
             prefetch_taken=prefetch.stats["taken"],
             prefetch_stale=prefetch.stats["stale"],
             prefetch_cancelled=prefetch.stats["cancelled"],
             wall_s=time.perf_counter() - t0)
    # the history JSON is a VIEW over the event stream now: same legacy
    # fields (round/loss/consensus_sq/tau1/tau2/round_s, plan_events, the
    # realized [tau1, tau2] schedule rows, schedule_mode, compile counts)
    # plus schema_version.
    history = history_view(tel.events)
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)
        print(f"history -> {args.history_out}")
    if args.telemetry_out:
        print(f"telemetry -> {args.telemetry_out} "
              f"({len(tel.events)} events)")
    tel.close()
    print("done")


if __name__ == "__main__":
    main()
