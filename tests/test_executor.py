"""Recompile-free executor tests (repro.core.executor + dynamic taus).

Pins the three tentpole properties:
  * a dynamic-(tau1, tau2) round is BITWISE equal to the static round in
    model state (params / opt_state / hat_params) and consensus metric on
    both engines, all paths (plain, CHOCO/C-DFL, kernels, schedules) —
    the scalar loss METRIC is allowed ~1 ulp (XLA associates the
    tau1-length vs tau1_max-length loss reduction differently);
  * a K-round superstep equals K sequential round_fn calls, including the
    fold_in RNG discipline and round_idx advance;
  * a forced (tau1, tau2) re-plan triggers ZERO new XLA compilations
    (trace-counter instrumentation), while K-shape changes and the static
    fallback cache compile exactly once per key.

Sparse-engine parity (shard_map + ppermute, kernels) needs 8 fake devices,
so it runs in a subprocess like tests/test_multidevice.py.
"""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DFLConfig, HostPrefetcher, MetricsBuffer,
                        RoundExecutor, init_state, make_compressor,
                        make_round_fn, ring, stack_round_batches)
from repro.core.topology import from_adjacency
from repro.optim import momentum_sgd, sgd

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


def assert_state_bitwise(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Dynamic taus == static taus (dense engine; sparse in the subprocess test)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comp,opt_name", [
    (None, "sgd"), ("qsgd", "sgd"), ("top_k", "momentum"),
])
def test_dynamic_round_equals_static_round(comp, opt_name):
    opt = sgd(0.1) if opt_name == "sgd" else momentum_sgd(0.1)
    compressor = make_compressor(comp) if comp else None
    cfg_static = DFLConfig(tau1=3, tau2=2, topology=ring(N),
                           compression=compressor, gamma=0.5)
    cfg_max = DFLConfig(tau1=5, tau2=4, topology=ring(N),
                        compression=compressor, gamma=0.5)
    st = fresh_state(opt, compressed=compressor is not None)
    full = batches_for(5)
    ref, m_ref = jax.jit(make_round_fn(cfg_static, noisy_loss, opt))(
        st, full[:3])
    dyn = jax.jit(make_round_fn(cfg_max, noisy_loss, opt, dynamic_taus=True))
    out, m_dyn = dyn(st, full, jnp.int32(3), jnp.int32(2))
    assert_state_bitwise(ref.params, out.params)
    assert_state_bitwise(ref.opt_state, out.opt_state)
    if compressor is not None:
        assert_state_bitwise(ref.hat_params, out.hat_params)
    assert int(out.round_idx) == 1
    np.testing.assert_array_equal(np.asarray(m_ref["consensus_sq"]),
                                  np.asarray(m_dyn["consensus_sq"]))
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_dyn["loss"]),
                               rtol=1e-6)


def test_dynamic_round_at_maxima_and_tau2_zero():
    """The bounds themselves and the no-gossip edge both dispatch against
    the same executable."""
    opt = sgd(0.1)
    cfg_max = DFLConfig(tau1=4, tau2=3, topology=ring(N))
    dyn = jax.jit(make_round_fn(cfg_max, noisy_loss, opt, dynamic_taus=True))
    full = batches_for(4)
    st = fresh_state(opt)
    for (t1, t2) in [(4, 3), (1, 0), (2, 3)]:
        cfg_s = DFLConfig(tau1=t1, tau2=t2, topology=ring(N))
        ref, _ = jax.jit(make_round_fn(cfg_s, noisy_loss, opt))(st, full[:t1])
        out, _ = dyn(st, full, jnp.int32(t1), jnp.int32(t2))
        assert_state_bitwise(ref.params, out.params)
    assert dyn._cache_size() == 1   # one executable served all three


def test_dynamic_round_topology_schedule_parity():
    """Round-varying topologies keep working under dynamic taus (the
    lax.switch branches take the dynamic trip count)."""
    adj = np.zeros((N, N), np.int64)
    for i in range(0, N, 2):
        j = (i + 1) % N
        adj[i, j] = adj[j, i] = 1
    m0 = from_adjacency("m0", adj)
    sched = (m0, ring(N))
    opt = sgd(0.1)
    cfg_s = DFLConfig(tau1=2, tau2=2, topology=m0, topology_schedule=sched)
    cfg_max = DFLConfig(tau1=3, tau2=3, topology=m0, topology_schedule=sched)
    st = fresh_state(opt)
    full = batches_for(3)
    rf_s = jax.jit(make_round_fn(cfg_s, noisy_loss, opt))
    rf_d = jax.jit(make_round_fn(cfg_max, noisy_loss, opt, dynamic_taus=True))
    ref = out = st
    for _ in range(2):   # two rounds: both schedule branches execute
        ref, _ = rf_s(ref, full[:2])
        out, _ = rf_d(out, full, jnp.int32(2), jnp.int32(2))
    assert_state_bitwise(ref.params, out.params)


def test_dense_power_rejects_dynamic_taus():
    cfg = DFLConfig(tau1=2, tau2=2, topology=ring(N),
                    mixing_impl="dense_power")
    with pytest.raises(ValueError, match="dense_power"):
        make_round_fn(cfg, noisy_loss, sgd(0.1), dynamic_taus=True)


# ---------------------------------------------------------------------------
# Fused supersteps
# ---------------------------------------------------------------------------


def test_superstep_equals_sequential_rounds():
    """K fused rounds == K sequential round_fn calls: params bitwise,
    per-round stacked metrics, round_idx advanced K, rng unchanged — the
    fold_in discipline derives every key from (rng, round_idx), so equality
    across MULTIPLE rounds is exactly the RNG-discipline check."""
    opt = sgd(0.1)
    cfg_s = DFLConfig(tau1=2, tau2=1, topology=ring(N))
    rf = jax.jit(make_round_fn(cfg_s, noisy_loss, opt))
    per_round = [batches_for(2, seed=10 + i) for i in range(4)]
    ref = fresh_state(opt)
    ref_metrics = []
    for b in per_round:
        ref, m = rf(ref, b)
        ref_metrics.append(m)

    ex = RoundExecutor(DFLConfig(tau1=3, tau2=2, topology=ring(N)),
                       noisy_loss, opt)
    stacked = stack_round_batches(per_round, tau1_max=3)
    out, m = ex.dispatch(fresh_state(opt), stacked, 2, 1)
    assert_state_bitwise(ref.params, out.params)
    assert int(out.round_idx) == 4
    np.testing.assert_array_equal(jax.random.key_data(out.rng),
                                  jax.random.key_data(fresh_state(opt).rng))
    assert m["loss"].shape == (4,)
    for i, mr in enumerate(ref_metrics):
        np.testing.assert_array_equal(np.asarray(mr["consensus_sq"]),
                                      np.asarray(m["consensus_sq"])[i])
        np.testing.assert_allclose(float(mr["loss"]),
                                   float(m["loss"][i]), rtol=1e-6)


def test_trajectory_superstep_equals_sequential_rounds():
    """A heterogeneous [K, 2] trajectory fused into one superstep equals
    the same schedule run as K sequential STATIC rounds (each jitted at
    its own (tau1, tau2)): params bitwise, metrics tagged with the
    realized schedule, RNG fold_in discipline intact across the mixed
    rounds. Plain and C-DFL (stochastic QSGD exercises the per-round key
    folding)."""
    from repro.core import make_compressor

    schedule = [(2, 1), (3, 0), (1, 2), (3, 2)]
    for comp_name in (None, "qsgd"):
        comp = make_compressor(comp_name) if comp_name else None
        opt = sgd(0.1)
        per_round = [batches_for(3, seed=20 + i) for i in range(len(schedule))]
        ref = fresh_state(opt, compressed=comp is not None)
        for b, (t1, t2) in zip(per_round, schedule):
            cfg_s = DFLConfig(tau1=t1, tau2=t2, topology=ring(N),
                              compression=comp, gamma=0.5)
            ref, _ = jax.jit(make_round_fn(cfg_s, noisy_loss, opt))(
                ref, b[:t1])
        ex = RoundExecutor(DFLConfig(tau1=3, tau2=2, topology=ring(N),
                                     compression=comp, gamma=0.5),
                           noisy_loss, opt)
        stacked = stack_round_batches(per_round, tau1_max=3)
        out, m = ex.dispatch_trajectory(
            fresh_state(opt, compressed=comp is not None), stacked,
            np.array(schedule, np.int32))
        assert_state_bitwise(ref.params, out.params)
        if comp is not None:
            assert_state_bitwise(ref.hat_params, out.hat_params)
        assert int(out.round_idx) == len(schedule)
        # metrics carry the REALIZED per-round schedule
        np.testing.assert_array_equal(np.asarray(m["tau1"]),
                                      [t1 for t1, _ in schedule])
        np.testing.assert_array_equal(np.asarray(m["tau2"]),
                                      [t2 for _, t2 in schedule])


def test_trajectory_shares_executable_with_uniform_dispatch():
    """Heterogeneous trajectories ride the SAME compiled executable as
    uniform dispatches — schedule heterogeneity never compiles."""
    opt = sgd(0.1)
    ex = RoundExecutor(DFLConfig(tau1=4, tau2=3, topology=ring(N)),
                       noisy_loss, opt)
    stacked = stack_round_batches([batches_for(4, seed=i) for i in range(3)],
                                  tau1_max=4)
    st, _ = ex.dispatch(fresh_state(opt), stacked, 2, 2)
    assert ex.compile_count == 1
    st, _ = ex.dispatch_trajectory(
        st, stacked, np.array([(4, 3), (1, 0), (2, 1)], np.int32))
    st, _ = ex.dispatch_trajectory(
        st, stacked, np.array([(1, 1), (4, 0), (3, 3)], np.int32))
    assert ex.compile_count == 1


def test_trajectory_static_fallback_segments():
    """dynamic=False plays a trajectory as contiguous uniform segments
    through the keyed cache: one compile per distinct (tau1, tau2), model
    state identical to the dynamic path."""
    opt = sgd(0.1)
    schedule = np.array([(2, 1), (2, 1), (3, 2)], np.int32)
    stacked = stack_round_batches([batches_for(3, seed=i) for i in range(3)],
                                  tau1_max=3)
    dyn = RoundExecutor(DFLConfig(tau1=3, tau2=2, topology=ring(N)),
                        noisy_loss, opt)
    want, m_dyn = dyn.dispatch_trajectory(fresh_state(opt), stacked, schedule)
    ex = RoundExecutor(DFLConfig(tau1=3, tau2=2, topology=ring(N)),
                       noisy_loss, opt, dynamic=False)
    out, m = ex.dispatch_trajectory(fresh_state(opt), stacked, schedule)
    assert ex.compile_count == 2          # two distinct (tau1, tau2) keys
    assert_state_bitwise(want.params, out.params)
    np.testing.assert_array_equal(np.asarray(m["tau1"]), [2, 2, 3])
    np.testing.assert_array_equal(np.asarray(m["tau2"]), [1, 1, 2])
    assert m["loss"].shape == (3,)


def test_trajectory_validation():
    opt = sgd(0.1)
    ex = RoundExecutor(DFLConfig(tau1=3, tau2=2, topology=ring(N)),
                       noisy_loss, opt)
    stacked = stack_round_batches([batches_for(3)] * 2, tau1_max=3)
    st = fresh_state(opt)
    with pytest.raises(ValueError, match=r"\[K, 2\]"):
        ex.dispatch_trajectory(st, stacked, np.array([2, 1], np.int32))
    with pytest.raises(ValueError, match="K=2"):
        ex.dispatch_trajectory(st, stacked,
                               np.array([(2, 1)] * 3, np.int32))
    with pytest.raises(ValueError, match="tau1=4"):
        ex.dispatch_trajectory(st, stacked,
                               np.array([(2, 1), (4, 1)], np.int32))
    with pytest.raises(ValueError, match="tau2=3"):
        ex.dispatch_trajectory(st, stacked,
                               np.array([(2, 1), (2, 3)], np.int32))


def test_superstep_round_idx_continues_across_dispatches():
    opt = sgd(0.1)
    ex = RoundExecutor(DFLConfig(tau1=2, tau2=1, topology=ring(N)),
                       noisy_loss, opt)
    stacked = stack_round_batches([batches_for(2), batches_for(2, 3)], 2)
    st, _ = ex.dispatch(fresh_state(opt), stacked, 2, 1)
    st, _ = ex.dispatch(st, stacked, 2, 1)
    assert int(st.round_idx) == 4
    assert ex.rounds_dispatched == 4 and ex.dispatch_count == 2


# ---------------------------------------------------------------------------
# Zero-recompile property (trace-counter instrumentation)
# ---------------------------------------------------------------------------


def test_replan_triggers_zero_recompiles():
    """THE acceptance property: re-planning (tau1, tau2) mid-run dispatches
    against the already-compiled executable — compile_count stays put."""
    opt = sgd(0.1)
    ex = RoundExecutor(DFLConfig(tau1=5, tau2=4, topology=ring(N)),
                       noisy_loss, opt)
    stacked = stack_round_batches([batches_for(5)], tau1_max=5)
    st, _ = ex.dispatch(fresh_state(opt), stacked, 3, 2)
    assert ex.compile_count == 1
    for (t1, t2) in [(5, 4), (1, 0), (2, 3), (3, 2)]:   # forced re-plans
        st, _ = ex.dispatch(st, stacked, t1, t2)
    assert ex.compile_count == 1
    # a new K (batch leading dim) is a new shape: exactly one more compile.
    st, _ = ex.dispatch(
        st, stack_round_batches([batches_for(5), batches_for(5)], 5), 2, 2)
    assert ex.compile_count == 2


def test_static_fallback_compile_cache():
    """dynamic=False: one compile per distinct (tau1, tau2), cached."""
    opt = sgd(0.1)
    ex = RoundExecutor(DFLConfig(tau1=5, tau2=4, topology=ring(N)),
                       noisy_loss, opt, dynamic=False)
    stacked = stack_round_batches([batches_for(5)], tau1_max=5)
    st, _ = ex.dispatch(fresh_state(opt), stacked, 3, 2)
    st, _ = ex.dispatch(st, stacked, 3, 2)
    assert ex.compile_count == 1
    st, _ = ex.dispatch(st, stacked, 2, 2)      # new key -> one compile
    assert ex.compile_count == 2
    st, _ = ex.dispatch(st, stacked, 3, 2)      # cached
    assert ex.compile_count == 2
    # static slices off the padding, so it matches the static reference.
    cfg_s = DFLConfig(tau1=3, tau2=2, topology=ring(N))
    ref, _ = jax.jit(make_round_fn(cfg_s, noisy_loss, opt))(
        fresh_state(opt), batches_for(5)[:3])
    ex2 = RoundExecutor(DFLConfig(tau1=5, tau2=4, topology=ring(N)),
                        noisy_loss, opt, dynamic=False)
    out, _ = ex2.dispatch(fresh_state(opt), stacked, 3, 2)
    assert_state_bitwise(ref.params, out.params)


def test_dispatch_rejects_out_of_bounds_taus():
    opt = sgd(0.1)
    ex = RoundExecutor(DFLConfig(tau1=3, tau2=2, topology=ring(N)),
                       noisy_loss, opt)
    stacked = stack_round_batches([batches_for(3)], tau1_max=3)
    st = fresh_state(opt)
    with pytest.raises(ValueError, match="tau1=4"):
        ex.dispatch(st, stacked, 4, 1)
    with pytest.raises(ValueError, match="tau2=3"):
        ex.dispatch(st, stacked, 1, 3)
    with pytest.raises(ValueError, match="tau1=0"):
        ex.dispatch(st, stacked, 0, 1)


# ---------------------------------------------------------------------------
# Host-side pieces: batch stacking, prefetch, deferred metrics
# ---------------------------------------------------------------------------


def test_stack_round_batches_pads_and_checks():
    a = {"x": np.ones((2, 4)), "y": np.ones((2, 3, 2))}
    b = {"x": 2 * np.ones((2, 4)), "y": 2 * np.ones((2, 3, 2))}
    out = stack_round_batches([a, b], tau1_max=4)
    assert out["x"].shape == (2, 4, 4) and out["y"].shape == (2, 4, 3, 2)
    np.testing.assert_array_equal(np.asarray(out["x"][1, :2]), 2 * np.ones((2, 4)))
    np.testing.assert_array_equal(np.asarray(out["x"][:, 2:]), 0.0)
    with pytest.raises(AssertionError, match="tau1_max"):
        stack_round_batches([{"x": np.ones((5, 4))}], tau1_max=4)


def test_host_prefetcher_overlap_and_staleness():
    pf = HostPrefetcher()

    def build(r, k):
        time.sleep(0.01)
        return ("batches", r, k)

    pf.schedule(build, 3, 2, meta=(3, 2))
    assert pf.pending_meta == (3, 2)
    out, meta = pf.take()
    assert out == ("batches", 3, 2) and meta == (3, 2)
    assert pf.pending_meta is None
    # worker exceptions surface on take(), not in the background thread.
    pf.schedule(lambda: 1 / 0, meta="boom")
    with pytest.raises(ZeroDivisionError):
        pf.take()
    pf.schedule(build, 0, 1, meta="stale")
    pf.cancel()
    assert pf.pending_meta is None


def test_host_prefetcher_failure_paths():
    """Misuse is loud: double-schedule and take-without-schedule raise,
    a worker exception surfaces on take() (and counts as an error), and
    cancel()/mark_stale() keep the stats ledger honest."""
    from repro.obs import Telemetry

    tel = Telemetry()
    pf = HostPrefetcher(telemetry=tel)

    with pytest.raises(RuntimeError, match="nothing scheduled"):
        pf.take()

    pf.schedule(lambda: "ok", meta="a")
    with pytest.raises(RuntimeError, match="previous prefetch not taken"):
        pf.schedule(lambda: "ok2", meta="b")
    assert pf.take() == ("ok", "a")

    # worker exception: raised on take(), prefetcher stays usable after.
    pf.schedule(lambda: 1 / 0, meta="boom")
    with pytest.raises(ZeroDivisionError):
        pf.take()
    pf.schedule(lambda: "alive", meta="c")
    assert pf.take() == ("alive", "c")

    # cancel() after schedule joins the worker without surfacing results.
    pf.schedule(lambda: "discarded", meta="d")
    pf.cancel()
    assert pf.pending_meta is None
    pf.cancel()          # idempotent when nothing is pending
    pf.mark_stale()

    assert pf.stats == {"scheduled": 4, "taken": 2, "cancelled": 1,
                        "stale": 1, "errors": 1, "retries": 0}
    types = [e["type"] for e in tel.events]
    assert types.count("prefetch") >= 5   # 4 builds + cancel/stale instants


def test_metrics_buffer_defers_and_amortizes():
    buf = MetricsBuffer()
    assert buf.flush() == []
    m1 = {"loss": jnp.asarray([1.0, 2.0]), "consensus_sq": jnp.asarray([0.1, 0.2])}
    m2 = {"loss": jnp.asarray([3.0]), "consensus_sq": jnp.asarray([0.3])}
    # the window opens at the FIRST chunk's pre-dispatch stamp: on the
    # pinned jaxlib the CPU client executes inside dispatch, so a
    # push-time origin would measure ~zero wall-clock per round.
    buf.push(10, 2, 4, 1, m1, dispatched_at=time.perf_counter() - 0.3)
    buf.push(12, 1, 2, 2, m2)
    assert buf.pending_rounds == 3
    rows = buf.flush()
    assert [r["round"] for r in rows] == [10, 11, 12]
    assert [r["loss"] for r in rows] == [1.0, 2.0, 3.0]
    assert [r["tau1"] for r in rows] == [4, 4, 2]
    assert rows[0]["round_s"] == rows[2]["round_s"] >= 0.1  # 0.3s / 3
    assert buf.pending_rounds == 0 and buf.flush() == []


def test_metrics_buffer_uses_metric_carried_taus():
    """Executor metrics tag each round with its realized (tau1, tau2);
    the buffer's rows must report THOSE (heterogeneous trajectories), with
    the push-args scalars as the legacy fallback."""
    buf = MetricsBuffer()
    m = {"loss": jnp.asarray([1.0, 2.0, 3.0]),
         "tau1": jnp.asarray([2, 3, 1]), "tau2": jnp.asarray([1, 0, 2])}
    buf.push(5, 3, None, None, m, dispatched_at=time.perf_counter())
    rows = buf.flush()
    assert [(r["tau1"], r["tau2"]) for r in rows] == [(2, 1), (3, 0), (1, 2)]
    assert all(isinstance(r["tau1"], int) for r in rows)
    assert [r["loss"] for r in rows] == [1.0, 2.0, 3.0]


def test_executor_warmup_precompiles_without_stats():
    """warmup() pays the compile for a batch shape ahead of time, from
    shapes: the first real dispatch at that shape then adds no compile, and
    warmup leaves dispatch statistics and the caller's state untouched."""
    opt = sgd(0.1)
    ex = RoundExecutor(DFLConfig(tau1=3, tau2=2, topology=ring(N)),
                       noisy_loss, opt)
    st = fresh_state(opt)
    stacked = stack_round_batches([batches_for(3)] * 2, tau1_max=3)
    ex.warmup(st, stacked)
    assert ex.compile_count == 1
    assert ex.dispatch_count == 0 and ex.rounds_dispatched == 0
    out, _ = ex.dispatch(st, stacked, 3, 2)   # st still alive post-warmup
    assert ex.compile_count == 1
    assert int(out.round_idx) == 2


# ---------------------------------------------------------------------------
# Sparse engine (shard_map + ppermute): 8 fake devices -> subprocess
# ---------------------------------------------------------------------------

SPARSE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.substrate import make_mesh
from repro.core import (DFLConfig, RoundExecutor, init_state, make_compressor,
                        make_round_fn, ring, stack_round_batches)
from repro.optim import sgd

mesh = make_mesh((8,), ("data",))
N = 8
topo = ring(N)
opt = sgd(0.1)

def noisy_loss(p, b, k=None):
    jitter = 0.05 * jax.random.normal(k, p["w"].shape)
    return jnp.mean((p["w"][None] + jitter[None] - b) ** 2)

targets = jnp.linspace(-1, 1, N)[:, None] * jnp.ones((N, 17))
full = jnp.broadcast_to(targets[None], (4, N, 17))
full = full[:, :, None, :] * jnp.ones((4, N, 2, 17))
st0 = init_state({"w": jnp.zeros((17,))}, N, opt, jax.random.key(5))

# dynamic sparse round == static DENSE reference (the numerical oracle),
# plain and C-DFL (stochastic QSGD), kernels hot path included.
for comp, kernels, tag in [(None, False, "PLAIN"),
                           ("qsgd", False, "CDFL"),
                           ("qsgd", True, "KERNELS")]:
    compressor = make_compressor(comp) if comp else None
    cfg_s = DFLConfig(tau1=2, tau2=2, topology=topo, compression=compressor,
                      gamma=0.5)
    cfg_max = DFLConfig(tau1=4, tau2=3, topology=topo, compression=compressor,
                        gamma=0.5)
    st = init_state({"w": jnp.zeros((17,))}, N, opt, jax.random.key(7),
                    compressed=comp is not None)
    ref, m_ref = jax.jit(make_round_fn(cfg_s, noisy_loss, opt))(st, full[:2])
    dyn = jax.jit(make_round_fn(cfg_max, noisy_loss, opt, engine="sparse",
                                mesh=mesh, node_axes=("data",),
                                use_kernels=kernels, dynamic_taus=True))
    out, m_dyn = dyn(st, full, jnp.int32(2), jnp.int32(2))
    err = float(jnp.max(jnp.abs(ref.params["w"] - out.params["w"])))
    assert err < 1e-5, f"{tag} sparse dynamic mismatch: {err}"
    assert abs(float(m_ref["loss"]) - float(m_dyn["loss"])) < 1e-5
    print(f"SPARSE_DYN_{tag}_OK", err)

# K-round sparse superstep == sequential static sparse rounds, and a forced
# re-plan triggers zero recompiles on the sparse engine too.
cfg_s = DFLConfig(tau1=2, tau2=2, topology=topo)
rf = jax.jit(make_round_fn(cfg_s, noisy_loss, opt, engine="sparse",
                           mesh=mesh, node_axes=("data",)))
ref = st0
for _ in range(3):
    ref, _ = rf(ref, full[:2])
ex = RoundExecutor(DFLConfig(tau1=4, tau2=3, topology=topo), noisy_loss,
                   opt, engine="sparse", mesh=mesh, node_axes=("data",))
stacked = stack_round_batches([full] * 3, tau1_max=4)
out, m = ex.dispatch(st0, stacked, 2, 2)
err2 = float(jnp.max(jnp.abs(ref.params["w"] - out.params["w"])))
assert err2 < 1e-5, f"sparse superstep mismatch: {err2}"
assert int(out.round_idx) == 3 and m["loss"].shape == (3,)
assert ex.compile_count == 1
out, _ = ex.dispatch(out, stacked, 4, 1)   # re-plan: tau1-heavy
out, _ = ex.dispatch(out, stacked, 1, 3)   # re-plan: tau2-heavy
assert ex.compile_count == 1, ex.compile_count
print("SPARSE_SUPERSTEP_OK", err2)
print("SPARSE_ZERO_RECOMPILE_OK")

# heterogeneous [K, 2] trajectory on the sparse engine == the same
# schedule as sequential static DENSE rounds (the numerical oracle), and
# it rides the SAME executable as the uniform dispatches above. (st0 was
# DONATED by the dispatches above — fresh same-key states here.)
fresh = lambda: init_state({"w": jnp.zeros((17,))}, N, opt, jax.random.key(5))
schedule = [(2, 2), (3, 0), (1, 1)]
ref = fresh()
for (t1, t2) in schedule:
    cfg_s = DFLConfig(tau1=t1, tau2=t2, topology=topo)
    ref, _ = jax.jit(make_round_fn(cfg_s, noisy_loss, opt))(ref, full[:t1])
out, m = ex.dispatch_trajectory(fresh(), stacked, np.array(schedule, np.int32))
err3 = float(jnp.max(jnp.abs(ref.params["w"] - out.params["w"])))
assert err3 < 1e-5, f"sparse trajectory mismatch: {err3}"
assert list(np.asarray(m["tau1"])) == [2, 3, 1]
assert ex.compile_count == 1, ex.compile_count
print("SPARSE_TRAJECTORY_OK", err3)

# constrain guard: a >1-sized auto axis + constrain must raise loudly
# (the re-assertion would be silently dropped); a node-only mesh accepts
# and ignores it.
mesh42 = make_mesh((4, 2), ("data", "model"))
cfg4 = DFLConfig(tau1=2, tau2=1, topology=ring(4))
try:
    make_round_fn(cfg4, noisy_loss, sgd(0.1), constrain=lambda t: t,
                  engine="sparse", mesh=mesh42, node_axes=("data",))
except NotImplementedError as e:
    assert "constrain" in str(e)
    print("SPARSE_CONSTRAIN_GUARD_OK")
make_round_fn(DFLConfig(tau1=2, tau2=1, topology=topo), noisy_loss,
              sgd(0.1), constrain=lambda t: t, engine="sparse", mesh=mesh,
              node_axes=("data",))
print("SPARSE_CONSTRAIN_IGNORED_OK")
"""


@pytest.mark.slow
def test_sparse_executor_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SPARSE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ["SPARSE_DYN_PLAIN_OK", "SPARSE_DYN_CDFL_OK",
                "SPARSE_DYN_KERNELS_OK", "SPARSE_SUPERSTEP_OK",
                "SPARSE_ZERO_RECOMPILE_OK", "SPARSE_TRAJECTORY_OK",
                "SPARSE_CONSTRAIN_GUARD_OK", "SPARSE_CONSTRAIN_IGNORED_OK"]:
        assert tag in out.stdout, (tag, out.stdout, out.stderr[-2000:])
