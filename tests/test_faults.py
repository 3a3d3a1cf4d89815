"""Fault injection + sporadic participation: the robustness contract.

What must hold (and is asserted here):

* all-ones masks are the IDENTITY — the participation executor's
  widened rows produce BITWISE the legacy round (plain and CHOCO) on
  the dense engine in-process and on the sparse engine in a
  subprocess. Participation must never tax a healthy deployment.
* masked mixing stays symmetric doubly stochastic (weight folds onto
  both endpoints' diagonals), and a crashed node (node + incident
  edges masked) keeps its params bitwise frozen while the others move.
* ``FaultPlan`` is deterministic (seeded per-round), composable
  (AND-composition, crash masks incident edges), validates its fault
  references, and round-trips through the JSON spec.
* ``FaultPlan.episodes`` prices OVERLAPPING link faults into
  piecewise-constant composed tariffs (no later-episode clobbering);
  ``masked_round_cost`` prices the sporadic round over the surviving
  sets only.
* the ``Availability`` planning hook degenerates exactly to the legacy
  bound at full participation and prices tau2 = 0 outage rounds with a
  finite resume-drift credit.
* degraded infrastructure is honest: atomic checkpoints fall back past
  torn files, the prefetcher retries transient build failures with
  backoff and ``close()`` joins its worker on every exit path.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import zipfile

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import (DFLConfig, RoundExecutor, init_state,  # noqa: E402
                        make_compressor, ring, stack_round_batches)
from repro.core.executor import HostPrefetcher  # noqa: E402
from repro.core.mixing import masked_mixing_matrix  # noqa: E402
from repro.core.topology import fully_connected  # noqa: E402
from repro.faults import (FaultPlan, LinkFlap, LinkOutage,  # noqa: E402
                          NodeCrash, SporadicParticipation, StragglerDelay,
                          load_fault_spec)
from repro.optim import sgd  # noqa: E402

N = 4
DIM = 9


def noisy_loss(p, b, k=None):
    jitter = 0.05 * jax.random.normal(k, p["w"].shape)
    return jnp.mean((p["w"][None] + jitter[None] - b) ** 2)


def fresh_state(opt, key=3, compressed=False):
    return init_state({"w": jnp.zeros((DIM,))}, N, opt,
                      jax.random.key(key), compressed=compressed)


def batches_for(tau1, rounds=2):
    targets = jnp.linspace(-1, 1, N)[:, None] * jnp.ones((N, DIM))
    per_round = [jnp.broadcast_to(targets[None, :, None, :],
                                  (tau1, N, 2, DIM))] * rounds
    return stack_round_batches(per_round, tau1)


def state_leaves(state):
    """The numerical state: params / opt_state / hat_params (the typed
    PRNG key leaf is compared separately by the caller when needed)."""
    trees = [state.params, state.opt_state]
    if state.hat_params is not None:
        trees.append(state.hat_params)
    leaves = []
    for t in trees:
        leaves += jax.tree_util.tree_leaves(t)
    return leaves


def assert_state_bitwise(a, b):
    la, lb = state_leaves(a), state_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            "state leaves differ bitwise")


# ---------------------------------------------------------------------------
# all-ones masks == legacy round, bitwise (dense engine, in-process)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comp", [None, "qsgd"])
def test_all_ones_masks_bitwise_equal_legacy(comp):
    compressor = make_compressor(comp) if comp else None
    cfg = DFLConfig(tau1=3, tau2=2, topology=ring(N),
                    compression=compressor, gamma=0.5)
    opt = sgd(0.1)
    batches = batches_for(3)

    legacy = RoundExecutor(cfg, noisy_loss, opt, donate=False)
    part = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                         participation=True)
    st = fresh_state(opt, compressed=comp is not None)

    ref, m_ref = legacy.dispatch(st, batches, 3, 2)
    rows = np.concatenate(
        [np.tile(np.array([[3, 2]], np.int32), (2, 1)),
         np.ones((2, part.row_width - 2), np.int32)], axis=1)
    out, m = part.dispatch_trajectory(st, batches, rows)

    assert_state_bitwise(ref, out)
    assert np.array_equal(np.asarray(m_ref["loss"]), np.asarray(m["loss"]))
    assert list(np.asarray(m["active_nodes"])) == [N, N]
    assert list(np.asarray(m["masked_edges"])) == [0, 0]


def test_all_ones_auto_padding_equals_explicit_masks():
    """[K, 2] rows through a participation executor auto-pad to all-ones
    — dispatch() and narrow trajectories work unchanged."""
    cfg = DFLConfig(tau1=2, tau2=1, topology=ring(N))
    opt = sgd(0.1)
    part = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                         participation=True)
    st = fresh_state(opt)
    batches = batches_for(2)
    narrow, _ = part.dispatch_trajectory(
        st, batches, np.array([[2, 1], [2, 1]], np.int32))
    wide_rows = np.concatenate(
        [np.tile(np.array([[2, 1]], np.int32), (2, 1)),
         np.ones((2, part.row_width - 2), np.int32)], axis=1)
    wide, _ = part.dispatch_trajectory(st, batches, wide_rows)
    assert_state_bitwise(narrow, wide)


# ---------------------------------------------------------------------------
# masked semantics: crash freezes the node, masked mixing stays stochastic
# ---------------------------------------------------------------------------


def test_crashed_node_params_frozen_others_move():
    topo = ring(N)
    plan = FaultPlan(topo, (NodeCrash(node=2, r_start=0, r_stop=1),))
    cfg = DFLConfig(tau1=2, tau2=1, topology=topo)
    opt = sgd(0.1)
    part = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                         participation=True)
    st = fresh_state(opt)
    rows = plan.mask_trajectory(np.array([[2, 1]], np.int32))
    out, m = part.dispatch_trajectory(st, batches_for(2, rounds=1), rows)

    before = np.asarray(st.params["w"])
    after = np.asarray(out.params["w"])
    # node 2: no local step AND all incident edges masked -> self-weight
    # folds to 1.0 -> params bitwise frozen. Everyone else learned.
    assert np.array_equal(before[2], after[2])
    for i in (0, 1, 3):
        assert not np.array_equal(before[i], after[i])
    assert int(np.asarray(m["active_nodes"])[0]) == N - 1
    assert int(np.asarray(m["masked_edges"])[0]) == 2


def test_masked_mixing_matrix_row_stochastic_and_identity():
    for topo in (ring(8), fully_connected(5)):
        e = topo.num_edges
        # all-ones: bitwise the static matrix.
        cm_on = masked_mixing_matrix(topo, jnp.ones((e,), jnp.int32),
                                     jnp.float32)
        assert np.array_equal(np.asarray(cm_on),
                              np.asarray(topo.mixing, np.float32))
        # arbitrary mask: symmetric doubly stochastic, masked edges zero.
        mask = np.ones(e, np.int32)
        mask[: e // 2] = 0
        cm = np.asarray(masked_mixing_matrix(
            topo, jnp.asarray(mask), jnp.float32))
        assert np.allclose(cm.sum(0), 1.0, atol=1e-6)
        assert np.allclose(cm.sum(1), 1.0, atol=1e-6)
        assert np.allclose(cm, cm.T, atol=1e-6)
        for (i, j), m in zip(topo.edges(), mask):
            if not m:
                assert cm[i, j] == 0.0 and cm[j, i] == 0.0


# ---------------------------------------------------------------------------
# FaultPlan: determinism, composition, validation, spec roundtrip
# ---------------------------------------------------------------------------


def test_fault_plan_deterministic_and_composed():
    topo = ring(8)
    plan = FaultPlan(topo, (
        NodeCrash(node=3, r_start=2, r_stop=5),
        LinkOutage(edges=((0, 1),), r_start=4, r_stop=6),
        SporadicParticipation(p_node=0.7, p_edge=0.6, r_start=6, r_stop=9),
    ), seed=11)

    # deterministic: same plan, same round -> same masks; rounds differ.
    for r in range(9):
        nm1, em1 = plan.masks(r)
        nm2, em2 = plan.masks(r)
        assert np.array_equal(nm1, nm2) and np.array_equal(em1, em2)
    nm6, _ = plan.masks(6)
    nm7, _ = plan.masks(7)
    nm8, _ = plan.masks(8)
    assert not (np.array_equal(nm6, nm7) and np.array_equal(nm7, nm8)), (
        "sporadic masks should vary across rounds")

    # round 1: nothing active.
    nm, em = plan.masks(1)
    assert nm.sum() == 8 and em.sum() == topo.num_edges

    # round 4: crash (node 3 + its 2 incident edges) AND the outage edge.
    nm, em = plan.masks(4)
    assert nm[3] == 0 and nm.sum() == 7
    down = {e for e, m in zip(topo.edges(), em) if not m}
    assert down == {(2, 3), (3, 4), (0, 1)}

    # seed changes the sporadic draw only.
    other = FaultPlan(topo, plan.faults, seed=12)
    assert np.array_equal(other.masks(4)[0], nm)
    assert any(not np.array_equal(other.masks(r)[0], plan.masks(r)[0])
               for r in range(6, 9))


def test_fault_plan_validation():
    topo = ring(4)
    with pytest.raises(ValueError, match="node"):
        FaultPlan(topo, (NodeCrash(node=9, r_start=0, r_stop=1),))
    with pytest.raises(ValueError, match="edge"):
        FaultPlan(topo, (LinkOutage(edges=((0, 2),), r_start=0, r_stop=1),))
    with pytest.raises(ValueError):
        NodeCrash(node=0, r_start=3, r_stop=3)   # empty window
    with pytest.raises(ValueError):
        LinkFlap(edge=(0, 1), period=2, up_rounds=2, r_start=0, r_stop=4)


def test_fault_plan_spec_roundtrip(tmp_path):
    topo = ring(8)
    plan = FaultPlan(topo, (
        NodeCrash(node=1, r_start=0, r_stop=3),
        StragglerDelay(node=2, slowdown=3, r_start=0, r_stop=9),
        LinkFlap(edge=(4, 5), period=3, up_rounds=1, r_start=2, r_stop=8),
    ), seed=5)
    spec = plan.to_spec()
    again = FaultPlan.from_spec(topo, spec)
    assert again.to_spec() == spec
    for r in range(9):
        a, b = plan.masks(r), again.masks(r)
        assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])

    # load_fault_spec: inline JSON and @file agree.
    inline = load_fault_spec(json.dumps(spec))
    path = tmp_path / "faults.json"
    path.write_text(json.dumps(spec))
    assert load_fault_spec(f"@{path}") == inline == spec
    with pytest.raises(ValueError, match="faults"):
        load_fault_spec("{}")


def test_mask_trajectory_widens_rows():
    topo = ring(4)
    plan = FaultPlan(topo, (NodeCrash(node=0, r_start=1, r_stop=2),))
    taus = np.array([[2, 1], [3, 0], [1, 1]], np.int32)
    rows = plan.mask_trajectory(taus)
    assert rows.shape == (3, 2 + 4 + topo.num_edges)
    assert np.array_equal(rows[:, :2], taus)
    assert rows[0, 2:].sum() == 4 + topo.num_edges      # round 0 healthy
    assert rows[1, 2 + 0] == 0                           # round 1 crash
    # round offset shifts the fault window.
    rows_off = plan.mask_trajectory(taus, round0=1)
    assert rows_off[0, 2 + 0] == 0


# ---------------------------------------------------------------------------
# pricing: composed episodes + masked_round_cost
# ---------------------------------------------------------------------------


def _unit_testbed():
    from repro.planner import (ComputeModel, CostModel, LinkModel,
                               WirelessLinks)
    topo = ring(8)
    model_bits = 32.0
    link = WirelessLinks(default=LinkModel(bytes_per_s=model_bits / 8.0))
    base = CostModel(compute=ComputeModel(step_flops=1.0, flops_per_s=1.0),
                     link=link, topology=topo, model_bits=model_bits)
    return topo, base


def test_episodes_compose_overlapping_link_faults():
    """Overlapping crash + flap windows must COMPOSE their tariffs (the
    naive one-episode-per-fault encoding lets the later link table
    clobber the earlier one)."""
    topo, base = _unit_testbed()
    plan = FaultPlan(topo, (
        NodeCrash(node=0, r_start=0, r_stop=10),
        LinkFlap(edge=(3, 4), period=2, up_rounds=1, r_start=5, r_stop=10),
    ))
    proc = plan.cost_process(base, seconds_per_round=1.0, residual=1e-3)
    base_t = base.round_cost(1, 1).time_s
    # inside the overlap, BOTH tariffs bite: a synchronous round pays the
    # crash's dead-edge residual (~1000x) regardless of the flap.
    overlap = proc.at(7.0).round_cost(1, 1).time_s
    crash_only = proc.at(2.0).round_cost(1, 1).time_s
    assert crash_only > base_t * 100
    assert overlap >= crash_only
    # after every window the base tariff returns.
    assert proc.at(11.0).round_cost(1, 1).time_s == pytest.approx(base_t)


def test_straggler_episode_scales_compute():
    topo, base = _unit_testbed()
    plan = FaultPlan(topo, (
        StragglerDelay(node=1, slowdown=4, r_start=2, r_stop=6),))
    proc = plan.cost_process(base, seconds_per_round=1.0)
    t_in = proc.at(3.0).round_cost(4, 0).time_s
    t_out = proc.at(8.0).round_cost(4, 0).time_s
    assert t_in == pytest.approx(4.0 * t_out)


def test_masked_round_cost_prices_surviving_sets():
    topo, base = _unit_testbed()
    full = base.round_cost(2, 1)
    same = base.masked_round_cost(2, 1, active_nodes=range(8),
                                  active_edges=topo.edges())
    assert same.time_s == pytest.approx(full.time_s)
    assert same.wire_bits == pytest.approx(full.wire_bits)

    # dead node: compute still runs (others), its edges priced out.
    edges = [e for e in topo.edges() if 0 not in e]
    rc = base.masked_round_cost(2, 1, active_nodes=range(1, 8),
                                active_edges=edges)
    assert rc.time_s == pytest.approx(full.time_s)  # max over active edges
    assert rc.wire_bits < full.wire_bits

    # nobody home: the round is free.
    empty = base.masked_round_cost(2, 1, active_nodes=[], active_edges=[])
    assert empty.time_s == 0.0 and empty.energy_j == 0.0

    # gossip-free masked round: no active edge -> no gossip time.
    comp_only = base.masked_round_cost(2, 1, active_nodes=range(8),
                                      active_edges=[])
    assert comp_only.time_s == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# planning: Availability degenerates exactly, prices outage rounds
# ---------------------------------------------------------------------------


def test_availability_bound_degenerates_and_prices_sporadic():
    from repro.planner.bounds import (Availability, expected_mixing,
                                      predicted_loss_decrement,
                                      sporadic_zeta)
    from repro.core.topology import zeta as spectral_zeta
    topo = ring(8)
    kw = dict(topology=topo, sigma=0.5, T=200, f_gap=1.0)

    legacy = predicted_loss_decrement(4, 2, **kw)
    full = predicted_loss_decrement(4, 2, availability=Availability(), **kw)
    assert legacy == full   # exact degeneration, same eta/terms

    degraded = predicted_loss_decrement(
        4, 2, availability=Availability(node_rate=0.6, edge_rate=0.5), **kw)
    assert degraded.bound > legacy.bound

    # tau2 = 0 in a DEGRADED regime with a resume credit is finite (the
    # legacy bound is inf for n > 1: a full-participation Availability
    # degenerates exactly, resume credit included), and still worse than
    # actually gossiping.
    outage = predicted_loss_decrement(
        4, 0, availability=Availability(edge_rate=0.9, resume_tau2=2.0),
        **kw)
    assert np.isfinite(outage.bound)
    # the credit RANKS: expecting fewer gossip steps on resume banks
    # more drift, so the bound must be monotonically worse.
    slower_resume = predicted_loss_decrement(
        4, 0, availability=Availability(edge_rate=0.9, resume_tau2=0.5),
        **kw)
    assert slower_resume.bound > outage.bound
    assert predicted_loss_decrement(
        4, 0, availability=Availability(resume_tau2=2.0), **kw
    ).bound == float("inf")

    # expected mixing: symmetric doubly stochastic at every rate; zeta
    # exact at rate 1, useless (1.0) at rate 0.
    for rate in (0.0, 0.3, 1.0):
        em = expected_mixing(topo, rate)
        assert np.allclose(em.sum(0), 1.0) and np.allclose(em, em.T)
    assert sporadic_zeta(topo, 1.0) == pytest.approx(
        spectral_zeta(topo.mixing))
    assert sporadic_zeta(topo, 0.0) == pytest.approx(1.0)


def test_controller_estimates_availability_from_masks():
    from repro.planner import AdaptiveController, Budget, unit_cost_model
    from repro.planner.bounds import Availability
    topo = ring(4)
    ctl = AdaptiveController(
        Budget(wall_clock_s=50.0),
        unit_cost_model(topo, 1.0, engine="dense", rep_dim=8),
        sigma=0.5, f_gap=1.0)
    assert ctl.availability() is None
    plan = FaultPlan(topo, (NodeCrash(node=1, r_start=0, r_stop=2),))
    for r in range(4):
        ctl.observe_participation(*plan.masks(r))
    avail = ctl.availability()
    assert isinstance(avail, Availability)
    assert avail.node_rate == pytest.approx((3 + 3 + 4 + 4) / 16)
    assert avail.edge_rate < 1.0
    # all-up observations only -> exact formulas (no availability hook).
    ctl2 = AdaptiveController(
        Budget(wall_clock_s=50.0),
        unit_cost_model(topo, 1.0, engine="dense", rep_dim=8),
        sigma=0.5, f_gap=1.0)
    ctl2.observe_participation(np.ones(4, np.int32), np.ones(4, np.int32))
    assert ctl2.availability() is None


# ---------------------------------------------------------------------------
# degraded infrastructure: atomic checkpoints, prefetcher retry/close
# ---------------------------------------------------------------------------


def test_restore_falls_back_past_torn_checkpoint(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    tree = {"w": np.arange(6, dtype=np.float32)}
    save_checkpoint(str(tmp_path), 1, tree)
    save_checkpoint(str(tmp_path), 2, {"w": tree["w"] * 2})
    # tear the newest file mid-archive (the pre-atomic failure mode).
    torn = tmp_path / "ckpt_00000002.npz"
    torn.write_bytes(torn.read_bytes()[:40])

    restored, step = restore_checkpoint(str(tmp_path), tree)
    assert step == 1
    assert np.array_equal(restored["w"], tree["w"])
    # an explicitly requested step is trusted -> loud failure.
    with pytest.raises((zipfile.BadZipFile, ValueError, OSError)):
        restore_checkpoint(str(tmp_path), tree, step=2)
    # nothing loadable at all -> FileNotFoundError naming the failures.
    (tmp_path / "ckpt_00000001.npz").write_bytes(b"junk")
    with pytest.raises(FileNotFoundError, match="step"):
        restore_checkpoint(str(tmp_path), tree)


def test_checkpoint_writes_are_atomic(tmp_path):
    from repro.checkpoint import save_checkpoint
    save_checkpoint(str(tmp_path), 7, {"w": np.zeros(3, np.float32)})
    names = sorted(os.listdir(tmp_path))
    assert names == ["ckpt_00000007.json", "ckpt_00000007.npz"], (
        "no temp files may survive a save")


def test_prefetcher_retries_transient_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "data"

    pf = HostPrefetcher(retries=2, backoff_s=0.001)
    pf.schedule(flaky, meta="m")
    assert pf.take() == ("data", "m")
    assert pf.stats["retries"] == 2 and pf.stats["errors"] == 0

    # retries exhausted -> the LAST error surfaces on take().
    pf.schedule(lambda: (_ for _ in ()).throw(OSError("down")), meta="x")
    with pytest.raises(OSError, match="down"):
        pf.take()


def test_prefetcher_close_joins_and_refuses_new_work():
    pf = HostPrefetcher(retries=5, backoff_s=0.05)
    pf.schedule(lambda: (_ for _ in ()).throw(OSError("never up")))
    pf.close()   # wakes the backoff wait, joins the worker
    assert pf.pending_meta is None
    with pytest.raises(RuntimeError, match="closed"):
        pf.schedule(lambda: "late")
    pf.close()   # idempotent


# ---------------------------------------------------------------------------
# sparse engine (8 fake devices, subprocess): parity + zero recompiles
# ---------------------------------------------------------------------------

SPARSE_FAULTS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.substrate import make_mesh
from repro.core import (DFLConfig, RoundExecutor, init_state,
                        make_compressor, ring, stack_round_batches)
from repro.faults import FaultPlan, NodeCrash, LinkOutage
from repro.optim import sgd

mesh = make_mesh((8,), ("data",))
N = 8
topo = ring(N)
opt = sgd(0.1)

def noisy_loss(p, b, k=None):
    jitter = 0.05 * jax.random.normal(k, p["w"].shape)
    return jnp.mean((p["w"][None] + jitter[None] - b) ** 2)

targets = jnp.linspace(-1, 1, N)[:, None] * jnp.ones((N, 17))
full = jnp.broadcast_to(targets[None, :, None, :], (3, N, 2, 17))
batches = stack_round_batches([full] * 2, tau1_max=3)
fresh = lambda k=5: init_state({"w": jnp.zeros((17,))}, N, opt,
                               jax.random.key(k))

cfg = DFLConfig(tau1=3, tau2=2, topology=topo)
plan = FaultPlan(topo, (NodeCrash(node=3, r_start=0, r_stop=1),
                        LinkOutage(edges=((6, 7),), r_start=1, r_stop=2)),
                 seed=0)
taus = np.array([[3, 2], [2, 1]], np.int32)
rows = plan.mask_trajectory(taus)

dense = RoundExecutor(cfg, noisy_loss, opt, donate=False,
                      participation=True)
sparse = RoundExecutor(cfg, noisy_loss, opt, engine="sparse", mesh=mesh,
                       node_axes=("data",), donate=False,
                       participation=True)

# masked trajectory: dense is the numerical oracle for sparse.
d_out, d_m = dense.dispatch_trajectory(fresh(), batches, rows)
s_out, s_m = sparse.dispatch_trajectory(fresh(), batches, rows)
err = float(jnp.max(jnp.abs(d_out.params["w"] - s_out.params["w"])))
assert err < 1e-5, f"masked sparse != dense: {err}"
assert list(np.asarray(s_m["active_nodes"])) == [7, 8]
assert list(np.asarray(s_m["masked_edges"])) == [2, 1]
print("SPARSE_MASKED_PARITY_OK", err)

# all-ones rows == legacy sparse executor, bitwise.
legacy = RoundExecutor(cfg, noisy_loss, opt, engine="sparse", mesh=mesh,
                       node_axes=("data",), donate=False)
ref, _ = legacy.dispatch(fresh(), batches, 3, 2)
ones = np.concatenate([np.tile(np.array([[3, 2]], np.int32), (2, 1)),
                       np.ones((2, sparse.row_width - 2), np.int32)], 1)
out, _ = sparse.dispatch_trajectory(fresh(), batches, ones)
assert np.array_equal(np.asarray(ref.params["w"]),
                      np.asarray(out.params["w"]))
print("SPARSE_ALLONES_BITWISE_OK")

# masks are schedule data: three different fault patterns, one compile.
assert sparse.compile_count == 1, sparse.compile_count
other = FaultPlan(topo, (NodeCrash(node=0, r_start=0, r_stop=2),), seed=1)
sparse.dispatch_trajectory(fresh(), batches, other.mask_trajectory(taus))
assert sparse.compile_count == 1, sparse.compile_count
print("SPARSE_MASKS_ZERO_RECOMPILE_OK")

# the masked executable still ships the full topology pair set (masks
# gate weights, not collectives).
from repro.analysis.audits import audit_collective_matching
low = sparse.lower_superstep(fresh(), batches, rows)
res = audit_collective_matching(low.compile().as_text(), topo)
assert res.ok, res.detail
print("SPARSE_MASKED_COLLECTIVES_OK")
"""


@pytest.mark.slow
def test_sparse_engine_fault_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SPARSE_FAULTS_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ["SPARSE_MASKED_PARITY_OK", "SPARSE_ALLONES_BITWISE_OK",
                "SPARSE_MASKS_ZERO_RECOMPILE_OK",
                "SPARSE_MASKED_COLLECTIVES_OK"]:
        assert tag in out.stdout, (tag, out.stdout, out.stderr[-2000:])
