"""Differential parity harness: the node-batched engine vs its oracles.

The batched engine's correctness story is parity-by-construction —
``BatchedSubstrate`` gathers the cohort's state rows, runs the SAME
``core.dfl.round_body`` a ``DenseSubstrate`` would, and scatters back —
so at small N, where all three engines can run the same rounds, the
harness asserts it directly:

  * **batched == dense BITWISE** on model state (params / opt_state /
    hat_params), round metrics, and the RNG fold_in discipline, across
    {plain, CHOCO-QSGD, CHOCO-TopK} x {full cohort, sampled
    cohort-as-masks} x {ring, torus}. The loss is noisy (per-node
    jitter keys) so a wrong fold would diverge, not just drift.
  * **batched == sparse at 1e-5** via the existing 8-fake-device
    subprocess pattern (ring only — the sparse engine needs a
    circulant topology). The repo's own dense<->sparse parity is
    tolerance-based (XLA associates reductions differently across
    shard_map boundaries), so the sparse leg inherits that tolerance;
    bitwise is reserved for the dense oracle.
  * **population > cohort**: non-cohort state rows are bitwise FROZEN
    through a sampled round, cohort rows move, and
    ``BatchedSubstrate.node_keys`` folds GLOBAL ids (a slot-indexed
    fold would decouple a node's noise stream from its identity).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BatchedSubstrate, DFLConfig, RoundExecutor,
                        init_state, make_compressor, ring, torus)
from repro.core.substrate import DenseSubstrate
from repro.optim import sgd

DIM = 7
TAU1, TAU2 = 2, 1
K = 3


def noisy_loss(p, b, k=None):
    jitter = 0.05 * jax.random.normal(k, p["w"].shape)
    return jnp.mean((p["w"] + jitter - b) ** 2)


def _compressor(name):
    if name == "qsgd":
        return make_compressor("qsgd", levels=4)
    if name == "top_k":
        return make_compressor("top_k", frac=0.5)
    return None


def _run(engine, topo, taus, comp_name, population=None, seed=1):
    comp = _compressor(comp_name)
    opt = sgd(0.1)
    cfg = DFLConfig(tau1=TAU1, tau2=TAU2, topology=topo, compression=comp,
                    gamma=0.5)
    n = topo.num_nodes
    state = init_state({"w": jnp.zeros((DIM,))}, population or n, opt,
                       jax.random.key(seed), compressed=comp is not None)
    kw = dict(population=population or n) if engine == "batched" else {}
    ex = RoundExecutor(cfg, noisy_loss, opt, engine=engine,
                       participation=engine == "dense", **kw)
    batches = jax.random.normal(jax.random.key(7), (K, TAU1, n, DIM))
    state, metrics = ex.dispatch_trajectory(state, batches, taus)
    return state, metrics


def assert_bitwise(a, b, what=""):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def _model_state(st):
    return (st.params, st.opt_state, st.hat_params)


def _rows(topo, sampled: bool):
    """(dense participation rows, batched cohort rows) for one matrix
    cell: full = plain [K, 2] both sides; sampled = identity cohort ids
    plus a seeded node-mask draw (cohort-as-masks — same round
    semantics both engines)."""
    n, e = topo.num_nodes, topo.num_edges
    plain = np.tile(np.array([[TAU1, TAU2]], np.int32), (K, 1))
    if not sampled:
        return plain, plain
    rng = np.random.default_rng(3)
    nm = rng.integers(0, 2, (K, n)).astype(np.int32)
    nm[:, 0] = 1   # never a fully-dead round
    ones_e = np.ones((K, e), np.int32)
    ids = np.tile(np.arange(n, dtype=np.int32), (K, 1))
    dense_rows = np.concatenate([plain, nm, ones_e], axis=1)
    batched_rows = np.concatenate([plain, ids, nm, ones_e], axis=1)
    return dense_rows, batched_rows


TOPOLOGIES = {"ring": lambda: ring(8), "torus": lambda: torus(2, 4)}


@pytest.mark.parametrize("comp_name", ["plain", "qsgd", "top_k"])
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["full-cohort", "sampled-as-masks"])
@pytest.mark.parametrize("topo_name", sorted(TOPOLOGIES))
def test_batched_equals_dense_bitwise(comp_name, sampled, topo_name):
    topo = TOPOLOGIES[topo_name]()
    dense_rows, batched_rows = _rows(topo, sampled)
    sd, md = _run("dense", topo, dense_rows, comp_name)
    sb, mb = _run("batched", topo, batched_rows, comp_name)
    assert_bitwise(_model_state(sd), _model_state(sb),
                   f"model state {topo_name}/{comp_name}")
    assert_bitwise(md, mb, f"metrics {topo_name}/{comp_name}")
    assert int(sb.round_idx) == K


def test_node_keys_fold_global_ids():
    """Cohort slot j's key must be fold_in(key, GLOBAL id), not slot
    index — a node's noise stream follows its identity across draws."""
    topo = ring(4)
    key = jax.random.key(11)
    ids = jnp.array([9, 2, 31, 17], jnp.int32)
    sub = BatchedSubstrate(topo, 32, ids)
    got = sub.node_keys(key)
    want = jnp.stack([jax.random.fold_in(key, int(i)) for i in ids])
    np.testing.assert_array_equal(
        jax.random.key_data(got), jax.random.key_data(want))
    # identity cohort degenerates to the dense fold exactly.
    full = BatchedSubstrate(topo, 4)
    np.testing.assert_array_equal(
        jax.random.key_data(full.node_keys(key)),
        jax.random.key_data(DenseSubstrate(topo).node_keys(key)))


def test_noncohort_rows_bitwise_frozen():
    """V > C: a sampled round must not touch (not even re-serialize
    through an op) any state row outside the cohort."""
    topo = ring(4)
    pop = 16
    opt = sgd(0.1)
    cfg = DFLConfig(tau1=TAU1, tau2=TAU2, topology=topo)
    state = init_state({"w": jnp.zeros((DIM,))}, pop, opt,
                       jax.random.key(2))
    # make rows distinguishable so "frozen" is a real claim.
    state = state._replace(params={"w": jnp.asarray(
        np.random.default_rng(0).normal(size=(pop, DIM)), jnp.float32)})
    before = np.asarray(state.params["w"]).copy()
    ex = RoundExecutor(cfg, noisy_loss, opt, engine="batched",
                       population=pop)
    ids = np.array([1, 5, 8, 14], np.int32)
    rows = np.concatenate([
        np.tile(np.array([[TAU1, TAU2]], np.int32), (K, 1)),
        np.tile(ids, (K, 1)),
        np.ones((K, topo.num_nodes + topo.num_edges), np.int32)], axis=1)
    batches = jax.random.normal(jax.random.key(7),
                                (K, TAU1, topo.num_nodes, DIM))
    out, _ = ex.dispatch_trajectory(state, batches, rows)
    after = np.asarray(out.params["w"])
    others = np.setdiff1d(np.arange(pop), ids)
    np.testing.assert_array_equal(after[others], before[others])
    assert not np.array_equal(after[ids], before[ids])


def test_cohort_trajectory_validation():
    topo = ring(4)
    opt = sgd(0.1)
    cfg = DFLConfig(tau1=TAU1, tau2=TAU2, topology=topo)
    ex = RoundExecutor(cfg, noisy_loss, opt, engine="batched",
                       population=8)
    assert ex.row_width == 2 + 2 * 4 + topo.num_edges
    base = np.tile(np.array([[TAU1, TAU2]], np.int32), (2, 1))
    masks = np.ones((2, 4 + topo.num_edges), np.int32)

    def rows_with(ids_row):
        ids = np.tile(np.asarray(ids_row, np.int32), (2, 1))
        return np.concatenate([base, ids, masks], axis=1)

    with pytest.raises(ValueError, match="unique"):
        ex._check_trajectory(rows_with([1, 1, 2, 3]), 2)
    with pytest.raises(ValueError, match="lie in"):
        ex._check_trajectory(rows_with([0, 1, 2, 8]), 2)
    # [K, 2] auto-pads to the identity cohort, all-active.
    padded = ex._check_trajectory(base, 2)
    np.testing.assert_array_equal(padded[:, 2:6],
                                  np.tile(np.arange(4), (2, 1)))
    assert (padded[:, 6:] == 1).all()
    with pytest.raises(ValueError, match="batched-engine parameter"):
        RoundExecutor(cfg, noisy_loss, opt, engine="dense", population=8)
    with pytest.raises(ValueError, match="population"):
        RoundExecutor(cfg, noisy_loss, opt, engine="batched")


# ---------------------------------------------------------------------------
# sparse leg: 8 fake devices -> subprocess (ring only: sparse needs a
# circulant topology). batched == dense BITWISE in-process there too;
# batched vs sparse inherits the repo's dense<->sparse 1e-5 tolerance.
# ---------------------------------------------------------------------------

SPARSE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.substrate import make_mesh
from repro.core import DFLConfig, RoundExecutor, init_state, ring
from repro.optim import sgd

N, DIM, TAU1, TAU2, K = 8, 7, 2, 1, 3
mesh = make_mesh((8,), ("data",))
topo = ring(N)
opt = sgd(0.1)

def noisy_loss(p, b, k=None):
    jitter = 0.05 * jax.random.normal(k, p["w"].shape)
    return jnp.mean((p["w"] + jitter - b) ** 2)

def leaves(st):
    return jax.tree_util.tree_leaves((st.params, st.opt_state))

cfg = DFLConfig(tau1=TAU1, tau2=TAU2, topology=topo)
batches = jax.random.normal(jax.random.key(7), (K, TAU1, N, DIM))
taus = np.tile(np.array([[TAU1, TAU2]], np.int32), (K, 1))

def run(engine, **kw):
    st = init_state({"w": jnp.zeros((DIM,))}, N, opt, jax.random.key(1))
    ex = RoundExecutor(cfg, noisy_loss, opt, engine=engine, **kw)
    st, m = ex.dispatch_trajectory(st, batches, taus)
    return st, m

sd, md = run("dense", participation=True)
sb, mb = run("batched", population=N)
ss, ms = run("sparse", mesh=mesh, node_axes=("data",))

for x, y in zip(leaves(sd), leaves(sb)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
np.testing.assert_array_equal(np.asarray(md["loss"]), np.asarray(mb["loss"]))
print("BATCHED_DENSE_BITWISE_OK")

err = max(float(jnp.max(jnp.abs(x - y)))
          for x, y in zip(leaves(sb), leaves(ss)))
assert err < 1e-5, f"batched vs sparse: {err}"
merr = float(np.max(np.abs(np.asarray(mb["loss"]) - np.asarray(ms["loss"]))))
assert merr < 1e-5, f"metrics: {merr}"
print("BATCHED_SPARSE_TOL_OK", err)
"""


@pytest.mark.slow
def test_batched_parity_sparse_leg():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SPARSE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ["BATCHED_DENSE_BITWISE_OK", "BATCHED_SPARSE_TOL_OK"]:
        assert tag in out.stdout, (tag, out.stdout, out.stderr[-2000:])
