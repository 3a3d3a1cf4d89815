"""Overlapped supersteps: the double-buffered gossip/compute pipeline.

Pins the PR's contract from executor to planner:

  * ``overlap="none"`` is BITWISE the legacy executor — the knob's default
    must not move a single bit on either engine, plain or CHOCO;
  * ``overlap="pipeline"`` equals a pure-Python one-round-stale-mixing
    reference (round k's local phase + round k-1's exchange folded late,
    drained after the scan) to float tolerance, including the CHOCO hat
    chain and the metrics' realized schedule;
  * drain semantics: a dispatched superstep returns fully-drained state, so
    chunked dispatches match per-chunk references and a restart from a
    checkpointed state (a fresh executor) continues bitwise — no gossip
    ever crosses a superstep/checkpoint boundary;
  * zero recompiles across trajectories in pipeline mode (the audits check
    the same property on the compiled artifact);
  * the planner prices the pipeline: max-form round time degenerating to
    additive at "none", the staleness penalty via ``stale_mixing_zeta``,
    and the roofline's ``predict_overlap`` arithmetic.

Sparse-engine parity needs 8 fake devices → subprocess, like
tests/test_executor.py.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DFLConfig, RoundExecutor, init_state, make_compressor,
                        ring, stack_round_batches)
from repro.core.dfl import gossip_phase, local_phase, round_keys
from repro.core.substrate import DenseSubstrate
from repro.optim import sgd

N = 8
DIM = 5


def noisy_loss(p, b, k=None):
    jitter = 0.02 * jax.random.normal(k, p["w"].shape)
    return jnp.mean((p["w"] + jitter - b) ** 2)


def batches_for(tau1, seed=2):
    return jax.random.normal(jax.random.key(seed), (tau1, N, DIM))


def fresh_state(opt, compressed=False, seed=1):
    return init_state({"w": jnp.zeros((DIM,))}, N, opt, jax.random.key(seed),
                      compressed=compressed)


def assert_model_state_bitwise(a, b):
    """params / opt_state / hat_params bitwise (NOT rng: typed keys)."""
    for x, y in zip(
            jax.tree_util.tree_leaves((a.params, a.opt_state, a.hat_params)),
            jax.tree_util.tree_leaves((b.params, b.opt_state, b.hat_params))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def stale_reference(cfg, opt, state, round_batches, taus):
    """Pure-Python one-round-stale-mixing oracle, drain included.

    Round k runs its tau1 local steps with round k's local key, then folds
    round k-1's exchange (round k-1's comm key / tau2) into the post-local
    params; the final in-flight exchange drains after the loop. Built from
    the same ``local_phase``/``gossip_phase`` stages the legacy round
    composes, so it is a reference for the SCHEDULE, not the numerics.
    """
    sub = DenseSubstrate(cfg.topology)
    params, opt_state, hat = state.params, state.opt_state, state.hat_params
    rng, r0 = state.rng, int(state.round_idx)
    buf = prev_t2 = None
    losses = []
    for i, ((t1, t2), b) in enumerate(zip(taus, round_batches)):
        r = r0 + i
        lk, _ = round_keys(rng, r)
        bt = np.zeros((cfg.tau1,) + b.shape[1:], np.float32)
        bt[: b.shape[0]] = np.asarray(b)
        z, opt_state, loss, _ = local_phase(
            cfg, noisy_loss, opt, sub, params, opt_state, lk,
            jnp.asarray(bt), tau1=jnp.asarray(int(t1), jnp.int32))
        losses.append(float(loss))
        if buf is not None:
            _, ck = round_keys(rng, r - 1)
            g, hat_g = gossip_phase(cfg, sub, buf, hat, ck, r - 1,
                                    tau2=jnp.asarray(prev_t2, jnp.int32))
            params = jax.tree_util.tree_map(
                lambda zl, gl, bl: zl + (gl - bl), z, g, buf)
            if cfg.is_compressed:
                hat = hat_g
        else:
            params = z
        buf = z
        prev_t2 = int(t2)
    r_end = r0 + len(taus)
    _, ck = round_keys(rng, r_end - 1)
    g, hat_d = gossip_phase(cfg, sub, buf, hat, ck, r_end - 1,
                            tau2=jnp.asarray(prev_t2, jnp.int32))
    params = jax.tree_util.tree_map(
        lambda pl, gl, bl: pl + (gl - bl), params, g, buf)
    if cfg.is_compressed:
        hat = hat_d
    return params, hat, losses


TAUS = np.array([[3, 2], [1, 1], [2, 2], [3, 0]], np.int32)


def _round_batches(taus, seed0=10):
    return [batches_for(int(t1), seed=seed0 + i)
            for i, (t1, _) in enumerate(taus)]


# ---------------------------------------------------------------------------
# overlap="none" is the legacy path, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comp", [None, "top_k"])
def test_overlap_none_bitwise_equals_legacy(comp):
    compressor = make_compressor(comp, frac=0.5) if comp else None
    cfg = DFLConfig(tau1=3, tau2=2, topology=ring(N),
                    compression=compressor, gamma=0.5)
    opt = sgd(0.1)
    rb = _round_batches(TAUS)
    batches = stack_round_batches(rb, cfg.tau1)
    c = compressor is not None
    legacy = RoundExecutor(cfg, noisy_loss, opt, donate=False)
    none = RoundExecutor(cfg, noisy_loss, opt, donate=False, overlap="none")
    sa, ma = legacy.dispatch_trajectory(fresh_state(opt, c), batches, TAUS)
    sb, mb = none.dispatch_trajectory(fresh_state(opt, c), batches, TAUS)
    assert_model_state_bitwise(sa, sb)
    np.testing.assert_array_equal(np.asarray(ma["loss"]),
                                  np.asarray(mb["loss"]))
    np.testing.assert_array_equal(np.asarray(ma["consensus_sq"]),
                                  np.asarray(mb["consensus_sq"]))
    # uniform dispatch rides the same executable in both executors too
    su, _ = legacy.dispatch(sa, batches, 2, 1)
    sv, _ = none.dispatch(sb, batches, 2, 1)
    assert_model_state_bitwise(su, sv)


# ---------------------------------------------------------------------------
# overlap="pipeline" == the one-round-stale-mixing reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comp", [None, "top_k"])
def test_pipeline_matches_stale_reference(comp):
    compressor = make_compressor(comp, frac=0.5) if comp else None
    cfg = DFLConfig(tau1=3, tau2=2, topology=ring(N),
                    compression=compressor, gamma=0.5)
    opt = sgd(0.1)
    rb = _round_batches(TAUS)
    batches = stack_round_batches(rb, cfg.tau1)
    c = compressor is not None
    ex = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                       overlap="pipeline")
    out, m = ex.dispatch_trajectory(fresh_state(opt, c), batches, TAUS)
    ref_p, ref_hat, ref_losses = stale_reference(
        cfg, opt, fresh_state(opt, c), rb, TAUS)
    np.testing.assert_allclose(np.asarray(out.params["w"]),
                               np.asarray(ref_p["w"]),
                               rtol=2e-6, atol=1e-7)
    if c:
        np.testing.assert_allclose(np.asarray(out.hat_params["w"]),
                                   np.asarray(ref_hat["w"]),
                                   rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(m["loss"]), np.asarray(ref_losses),
                               rtol=1e-5)
    # metrics still carry the realized schedule and rounds advanced K
    np.testing.assert_array_equal(np.asarray(m["tau1"]), TAUS[:, 0])
    np.testing.assert_array_equal(np.asarray(m["tau2"]), TAUS[:, 1])
    assert int(out.round_idx) == len(TAUS)


def test_pipeline_single_round_equals_legacy():
    """K=1: one local phase + one drained exchange IS the legacy round —
    the pipeline introduces staleness only BETWEEN rounds."""
    opt = sgd(0.1)
    cfg = DFLConfig(tau1=3, tau2=2, topology=ring(N))
    taus1 = np.array([[2, 2]], np.int32)
    b1 = stack_round_batches([batches_for(2, seed=33)], cfg.tau1)
    legacy = RoundExecutor(cfg, noisy_loss, opt, donate=False)
    pipe = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                         overlap="pipeline")
    s_leg, _ = legacy.dispatch_trajectory(fresh_state(opt), b1, taus1)
    s_pipe, _ = pipe.dispatch_trajectory(fresh_state(opt), b1, taus1)
    np.testing.assert_allclose(np.asarray(s_pipe.params["w"]),
                               np.asarray(s_leg.params["w"]),
                               rtol=2e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# drain semantics at superstep / checkpoint boundaries
# ---------------------------------------------------------------------------


def test_pipeline_drains_at_superstep_boundary():
    """A dispatched superstep returns fully-drained state: chunked
    dispatches equal the per-chunk stale reference, and a FRESH executor
    restarted from the first chunk's output (checkpoint/restore) continues
    bitwise — nothing is in flight across the boundary."""
    opt = sgd(0.1)
    cfg = DFLConfig(tau1=3, tau2=2, topology=ring(N))
    rb = _round_batches(TAUS)
    chunk_a = stack_round_batches(rb[:2], cfg.tau1)
    chunk_b = stack_round_batches(rb[2:], cfg.tau1)
    ex = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                       overlap="pipeline")
    mid, _ = ex.dispatch_trajectory(fresh_state(opt), chunk_a, TAUS[:2])
    end, _ = ex.dispatch_trajectory(mid, chunk_b, TAUS[2:])
    # per-chunk reference: each chunk drains, the next starts fresh
    p1, _, _ = stale_reference(cfg, opt, fresh_state(opt), rb[:2], TAUS[:2])
    np.testing.assert_allclose(np.asarray(mid.params["w"]),
                               np.asarray(p1["w"]), rtol=2e-6, atol=1e-7)
    ref_mid = fresh_state(opt)._replace(
        params=p1, round_idx=mid.round_idx)
    p2, _, _ = stale_reference(cfg, opt, ref_mid, rb[2:], TAUS[2:])
    np.testing.assert_allclose(np.asarray(end.params["w"]),
                               np.asarray(p2["w"]), rtol=4e-6, atol=1e-7)
    # restore: a brand-new executor picks up from `mid` identically
    ex2 = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                        overlap="pipeline")
    end2, _ = ex2.dispatch_trajectory(mid, chunk_b, TAUS[2:])
    assert_model_state_bitwise(end, end2)


# ---------------------------------------------------------------------------
# zero recompiles / validation / participation
# ---------------------------------------------------------------------------


def test_pipeline_zero_recompiles_across_trajectories():
    opt = sgd(0.1)
    cfg = DFLConfig(tau1=3, tau2=2, topology=ring(N))
    ex = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                       overlap="pipeline")
    batches = stack_round_batches(_round_batches(TAUS), cfg.tau1)
    st, _ = ex.dispatch_trajectory(fresh_state(opt), batches, TAUS)
    assert ex.compile_count == 1
    other = np.array([[1, 2], [3, 1], [2, 0], [1, 1]], np.int32)
    st, _ = ex.dispatch_trajectory(st, batches, other)
    st, _ = ex.dispatch(st, batches, 2, 2)   # uniform rides the same exe
    assert ex.compile_count == 1


def test_overlap_validation():
    opt = sgd(0.1)
    cfg = DFLConfig(tau1=3, tau2=2, topology=ring(N))
    with pytest.raises(ValueError, match="overlap"):
        RoundExecutor(cfg, noisy_loss, opt, overlap="bogus")
    with pytest.raises(ValueError, match="dynamic"):
        RoundExecutor(cfg, noisy_loss, opt, dynamic=False,
                      overlap="pipeline")
    from repro.core.dfl import make_pipeline_fns
    cfg_pow = DFLConfig(tau1=2, tau2=2, topology=ring(N),
                        mixing_impl="dense_power")
    with pytest.raises(ValueError, match="dense_power"):
        make_pipeline_fns(cfg_pow, noisy_loss, opt)
    from repro.planner import CostModel
    from repro.planner.cost import ComputeModel, LinkModel
    with pytest.raises(ValueError, match="overlap"):
        CostModel(compute=ComputeModel(1.0, 1.0), link=LinkModel(1.0),
                  topology=ring(N), model_bits=32.0, overlap="bogus")
    from repro.launch.steps import build_train_superstep
    with pytest.raises(ValueError, match="overlap"):
        build_train_superstep(None, "unused", None, overlap="bogus")


def test_participation_pipeline_all_ones_equals_plain():
    """Widened [K, 2+N+E] rows pipeline too: all-ones masks are bitwise
    the plain pipeline, and heterogeneous masks share the executable."""
    opt = sgd(0.1)
    cfg = DFLConfig(tau1=2, tau2=2, topology=ring(N))
    E = cfg.topology.num_edges
    K = 3
    rng = np.random.RandomState(0)
    rows = [[2, 2] + rng.binomial(1, 0.8, N).tolist()
            + rng.binomial(1, 0.8, E).tolist() for _ in range(K)]
    taus = np.asarray(rows, np.int32)
    rb = [batches_for(2, seed=10 + i) for i in range(K)]
    batches = stack_round_batches(rb, cfg.tau1)
    ex_p = RoundExecutor(cfg, noisy_loss, opt, participation=True,
                         overlap="pipeline", donate=False)
    st, _ = ex_p.dispatch_trajectory(fresh_state(opt), batches, taus)
    assert np.isfinite(np.asarray(st.params["w"])).all()
    ones = np.concatenate([taus[:, :2], np.ones((K, N + E), np.int32)],
                          axis=1)
    ex_plain = RoundExecutor(cfg, noisy_loss, opt, overlap="pipeline",
                             donate=False)
    s1, _ = ex_p.dispatch_trajectory(fresh_state(opt), batches, ones)
    s2, _ = ex_plain.dispatch_trajectory(fresh_state(opt), batches,
                                         taus[:, :2].copy())
    np.testing.assert_array_equal(np.asarray(s1.params["w"]),
                                  np.asarray(s2.params["w"]))
    n0 = ex_p.compile_count
    ex_p.dispatch_trajectory(st, batches, taus)
    assert ex_p.compile_count == n0


# ---------------------------------------------------------------------------
# observability: the gossip slice rides its own track
# ---------------------------------------------------------------------------


def test_pipeline_emits_overlap_events():
    from repro.obs import Telemetry
    from repro.obs.events import validate_events

    tel = Telemetry()
    opt = sgd(0.1)
    cfg = DFLConfig(tau1=3, tau2=2, topology=ring(N))
    ex = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                       overlap="pipeline", telemetry=tel)
    batches = stack_round_batches(_round_batches(TAUS), cfg.tau1)
    ex.dispatch_trajectory(fresh_state(opt), batches, TAUS)
    ov = [e for e in tel.events if e["type"] == "overlap"]
    assert len(ov) == 1
    assert ov[0]["track"] == "overlap" and ov[0]["dur"] is not None
    assert ov[0]["data"]["mode"] == "pipeline"
    assert ov[0]["data"]["k"] == len(TAUS)
    assert validate_events(tel.events) == []
    # overlap="none" stays silent on the overlap track
    tel2 = Telemetry()
    ex_n = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                         telemetry=tel2)
    ex_n.dispatch_trajectory(fresh_state(opt), batches, TAUS)
    assert not [e for e in tel2.events if e["type"] == "overlap"]


def test_run_report_aggregates_overlap():
    from repro.obs.events import make_event
    from repro.obs.report import format_report, run_report

    events = [
        make_event("run", 0.0, "run",
                   data={"schema": 3, "wall_start": 1.0}),
        make_event("overlap", 0.5, "overlap", name="gossip-inflight-k4",
                   dur=0.25, data={"mode": "pipeline", "k": 4,
                                   "dispatch": 1}),
        make_event("overlap", 1.0, "overlap", name="gossip-inflight-k4",
                   dur=0.15, data={"mode": "pipeline", "k": 4,
                                   "dispatch": 2}),
    ]
    rep = run_report(events)
    assert rep["overlap"] == {"supersteps": 2, "mode": "pipeline",
                              "inflight_s": pytest.approx(0.4)}
    assert "overlap: mode=pipeline over 2 superstep(s)" in format_report(rep)


# ---------------------------------------------------------------------------
# planner: the max-form round time and the staleness penalty
# ---------------------------------------------------------------------------


def test_cost_model_overlap_round_time():
    from repro.planner import unit_cost_model

    cm_none = unit_cost_model(ring(N), 4.0)
    cm_pipe = unit_cost_model(ring(N), 4.0, overlap="pipeline")
    t_c = cm_none.compute.t_step
    t_g = cm_none.t_gossip_step(None)
    for (t1, t2) in [(1, 1), (4, 2), (2, 4), (3, 0)]:
        none = cm_none.round_cost(t1, t2)
        pipe = cm_pipe.round_cost(t1, t2)
        assert none.time_s == pytest.approx(t1 * t_c + t2 * t_g)
        assert pipe.time_s == pytest.approx(
            t1 * t_c + max(0.0, t2 * t_g - t1 * t_c))
        # overlap hides time, never traffic or energy
        assert pipe.wire_bits == none.wire_bits
        assert pipe.time_s <= none.time_s
    # degeneration: no gossip, or no window, means additive exactly
    assert cm_pipe.round_cost(3, 0).time_s == cm_none.round_cost(3, 0).time_s
    assert cm_none.overlap_window(5) == 0.0
    assert cm_pipe.overlap_window(5) == pytest.approx(5 * t_c)


def test_masked_round_cost_overlap_window():
    """A fully-masked round computes nothing, so it hides nothing: the
    pipelined masked cost uses the MASKED compute window."""
    from repro.planner import unit_cost_model

    cm_none = unit_cost_model(ring(N), 4.0)
    cm_pipe = unit_cost_model(ring(N), 4.0, overlap="pipeline")
    # every node masked: zero compute window, so the pipelined price is
    # exactly the additive one — the wire is fully exposed
    dead_n = cm_none.masked_round_cost(2, 2, active_nodes=[])
    dead_p = cm_pipe.masked_round_cost(2, 2, active_nodes=[])
    assert dead_p.time_s == pytest.approx(dead_n.time_s)
    # unmasked: the pipeline hides up to the compute window
    live_n = cm_none.masked_round_cost(2, 2)
    live_p = cm_pipe.masked_round_cost(2, 2)
    t_c = cm_none.compute.t_step
    assert live_p.time_s == pytest.approx(
        2 * t_c + max(0.0, (live_n.time_s - 2 * t_c) - 2 * t_c))
    assert live_p.time_s <= live_n.time_s
    assert live_p.wire_bits == live_n.wire_bits


def test_stale_mixing_zeta():
    from repro.planner import stale_mixing_zeta
    from repro.planner.bounds import sporadic_zeta

    topo = ring(N)
    z0 = stale_mixing_zeta(topo, 0.0)
    assert z0 == pytest.approx(sporadic_zeta(topo, 1.0))
    z1 = stale_mixing_zeta(topo, 1.0)
    z3 = stale_mixing_zeta(topo, 3.0)
    assert z0 < z1 < z3 < 1.0
    with pytest.raises(ValueError, match="staleness"):
        stale_mixing_zeta(topo, -0.5)


def test_staleness_penalizes_loss_decrement():
    from repro.planner.bounds import predicted_loss_decrement

    kw = dict(T=200, f_gap=1.0)
    fresh = predicted_loss_decrement(4, 2, ring(N), 0.5, **kw)
    stale = predicted_loss_decrement(4, 2, ring(N), 0.5, staleness=1.0, **kw)
    assert stale.zeta > fresh.zeta
    assert stale.bound >= fresh.bound


def test_pipeline_plan_shifts_toward_compute():
    """On a gossip-dominated link the pipelined planner picks a schedule
    at least as tau1-heavy as the additive one — bigger local windows hide
    more wire, paying only the staleness penalty."""
    from repro.planner import (Budget, evaluate_grid, select_plan,
                               unit_cost_model)

    topo = ring(N)
    grid = [(1, 4), (1, 2), (1, 1), (2, 2), (2, 1), (4, 1), (8, 1)]
    sigma, f_gap = 0.5, 1.0
    cm_none = unit_cost_model(topo, 4.0)
    cm_pipe = unit_cost_model(topo, 4.0, overlap="pipeline")
    budget = Budget(wall_clock_s=cm_none.round_cost(2, 2).time_s * 60)
    p_none = select_plan(evaluate_grid(budget, cm_none, sigma=sigma,
                                       f_gap=f_gap, grid=grid))
    p_pipe = select_plan(evaluate_grid(budget, cm_pipe, sigma=sigma,
                                       f_gap=f_gap, grid=grid))
    ratio = lambda p: p.tau1 / max(p.tau2, 1)
    assert ratio(p_pipe) >= ratio(p_none)
    # and the pipelined winner's round really is cheaper than its additive
    # price — the planner is spending hidden seconds, not imaginary ones
    assert (cm_pipe.round_cost(p_pipe.tau1, p_pipe.tau2).time_s
            <= cm_none.round_cost(p_pipe.tau1, p_pipe.tau2).time_s)


def test_fitted_cost_model_preserves_overlap():
    from repro.planner import (AdaptiveController, Budget, unit_cost_model)

    cm = unit_cost_model(ring(N), 1.0, overlap="pipeline")
    ctrl = AdaptiveController(Budget(wall_clock_s=1e6), cm, sigma=0.5,
                              f_gap=1.0, grid=[(2, 2), (4, 1)])
    ctrl.initial_plan()
    ctrl.observe(2, 2, 1.0)
    ctrl.observe(4, 1, 1.3)
    assert ctrl.fitted_cost_model().overlap == "pipeline"


def test_predict_trajectory_matches_next_trajectory():
    """The controller's prediction contract (trajectory-mode prefetch):
    after observe_chunk and before new spend, predict_trajectory returns
    exactly what next_trajectory will emit — and mutates nothing."""
    from repro.planner import AdaptiveController, Budget, unit_cost_model

    cm = unit_cost_model(ring(N), 1.0)
    ctrl = AdaptiveController(Budget(wall_clock_s=1e5), cm, sigma=0.5,
                              f_gap=1.0, grid=[(1, 1), (2, 2), (4, 1)])
    ctrl.initial_plan()
    n_hist = len(ctrl.history)
    pred = ctrl.predict_trajectory(4)
    assert pred is not None
    pred2 = ctrl.predict_trajectory(4)
    np.testing.assert_array_equal(pred, pred2)       # pure read, stable
    assert len(ctrl.history) == n_hist               # no event emitted
    taus = ctrl.next_trajectory(4)
    np.testing.assert_array_equal(pred, taus)
    assert len(ctrl.history) == n_hist + 1           # commit DID emit
    # the contract survives a fit update: predict right after observing
    ctrl.observe_chunk([(int(a), int(b)) for a, b in taus], 12.0)
    pred = ctrl.predict_trajectory(4)
    taus2 = ctrl.next_trajectory(4, round_idx=4)
    np.testing.assert_array_equal(pred, taus2)


def test_predict_trajectory_exhaustion_returns_none():
    from repro.planner import AdaptiveController, Budget, unit_cost_model

    cm = unit_cost_model(ring(N), 1.0)
    ctrl = AdaptiveController(Budget(wall_clock_s=5.0), cm, sigma=0.5,
                              f_gap=1.0, grid=[(2, 2)])
    ctrl.initial_plan()
    ctrl.observe_chunk([(2, 2)] * 4, 100.0)          # budget gone
    assert ctrl.predict_trajectory(4) is None
    assert not ctrl.exhausted                        # prediction never sets it
    assert ctrl.next_trajectory(4, round_idx=4) is None
    assert ctrl.exhausted


# ---------------------------------------------------------------------------
# roofline: predicting the win before a round runs
# ---------------------------------------------------------------------------


def test_predict_overlap_arithmetic():
    from repro.launch.roofline import Roofline, predict_overlap

    local = Roofline(flops=2e12, hbm_bytes=1e9, collective_bytes=0.0,
                     chips=8)                         # compute-bound: 2.18ms
    gossip = Roofline(flops=0.0, hbm_bytes=0.0, collective_bytes=9e8,
                      chips=8)                        # 0.01s of wire
    p = predict_overlap(local, gossip, tau1=4, tau2=2)
    tl = max(local.compute_s, local.memory_s)
    tg = gossip.collective_s
    assert p.additive_s == pytest.approx(4 * tl + 2 * tg)
    assert p.pipelined_s == pytest.approx(4 * tl + max(0.0, 2 * tg - 4 * tl))
    assert p.hidden_s == pytest.approx(p.additive_s - p.pipelined_s)
    assert p.speedup == pytest.approx(p.additive_s / p.pipelined_s)
    assert p.hidden_s > 0                             # gossip-heavy: a win
    # measured-override calibration (what the bench does)
    pm = predict_overlap(local, gossip, tau1=4, tau2=2, t_local_step_s=0.5)
    assert pm.t_local_step_s == 0.5
    assert pm.t_gossip_step_s == pytest.approx(tg)
    # compute-dominated rounds degenerate: nothing left to hide
    big = predict_overlap(local, gossip, tau1=64, tau2=1)
    assert big.hidden_s == pytest.approx(big.tau2 * tg)
    assert big.pipelined_s == pytest.approx(64 * tl)
    d = p.as_dict()
    assert d["speedup"] == pytest.approx(p.speedup)


# ---------------------------------------------------------------------------
# sparse engine parity (8 fake devices -> subprocess)
# ---------------------------------------------------------------------------

OVERLAP_SPARSE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.substrate import make_mesh
from repro.core import (DFLConfig, RoundExecutor, init_state, make_compressor,
                        ring, stack_round_batches)
from repro.optim import sgd

N, DIM = 8, 17
mesh = make_mesh((8,), ("data",))
opt = sgd(0.05)

def noisy_loss(p, b, k=None):
    jitter = 0.02 * jax.random.normal(k, p["w"].shape)
    return jnp.mean((p["w"] + jitter - b) ** 2)

def fresh(compressed=False):
    return init_state({"w": jnp.zeros((DIM,))}, N, opt, jax.random.key(1),
                      compressed=compressed)

taus = np.array([[2, 2], [1, 1], [2, 0]], np.int32)
rb = [jax.random.normal(jax.random.key(10 + i), (int(t1), N, DIM))
      for i, (t1, _) in enumerate(taus)]

for comp_name in (None, "top_k"):
    comp = make_compressor(comp_name, frac=0.5) if comp_name else None
    cfg = DFLConfig(tau1=2, tau2=2, topology=ring(N), compression=comp)
    batches = stack_round_batches(rb, cfg.tau1)
    c = comp_name is not None
    kw = dict(donate=False)
    # overlap="none" is bitwise the legacy SPARSE executor
    ex_none = RoundExecutor(cfg, noisy_loss, opt, engine="sparse", mesh=mesh,
                            overlap="none", **kw)
    ex_legacy = RoundExecutor(cfg, noisy_loss, opt, engine="sparse",
                              mesh=mesh, **kw)
    s_n, _ = ex_none.dispatch_trajectory(fresh(c), batches, taus)
    s_l, _ = ex_legacy.dispatch_trajectory(fresh(c), batches, taus)
    for x, y in zip(
            jax.tree_util.tree_leaves((s_n.params, s_n.opt_state,
                                       s_n.hat_params)),
            jax.tree_util.tree_leaves((s_l.params, s_l.opt_state,
                                       s_l.hat_params))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    print(f"SPARSE_NONE_BITWISE_OK[{comp_name}]")

    # sparse pipeline == dense pipeline (the numerical oracle)
    ex_dp = RoundExecutor(cfg, noisy_loss, opt, overlap="pipeline", **kw)
    ex_sp = RoundExecutor(cfg, noisy_loss, opt, engine="sparse", mesh=mesh,
                          overlap="pipeline", **kw)
    s_dp, m_dp = ex_dp.dispatch_trajectory(fresh(c), batches, taus)
    s_sp, m_sp = ex_sp.dispatch_trajectory(fresh(c), batches, taus)
    err = float(jnp.max(jnp.abs(s_dp.params["w"] - s_sp.params["w"])))
    assert err < 1e-5, f"sparse pipeline mismatch[{comp_name}]: {err}"
    np.testing.assert_allclose(np.asarray(m_sp["loss"]),
                               np.asarray(m_dp["loss"]), rtol=1e-5)
    # zero recompiles across trajectories on the sparse pipeline too
    n0 = ex_sp.compile_count
    taus2 = np.array([[1, 2], [2, 1], [1, 0]], np.int32)
    ex_sp.dispatch_trajectory(s_sp, batches, taus2)
    assert ex_sp.compile_count == n0, ex_sp.compile_count
    print(f"SPARSE_PIPELINE_OK[{comp_name}]", err)
print("ALL_OK")
"""


@pytest.mark.slow
def test_sparse_overlap_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", OVERLAP_SPARSE_SCRIPT],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ["SPARSE_NONE_BITWISE_OK[None]",
                "SPARSE_NONE_BITWISE_OK[top_k]",
                "SPARSE_PIPELINE_OK[None]", "SPARSE_PIPELINE_OK[top_k]",
                "ALL_OK"]:
        assert tag in out.stdout, (tag, out.stdout, out.stderr[-2000:])
