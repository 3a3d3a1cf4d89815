"""Multi-device semantics (subprocess: needs fake devices before jax init).

Validates on an 8-device host mesh that:
 * the sparse ppermute gossip (shard_map) EXACTLY matches the dense einsum
   mixing for a circulant ring C;
 * a sharded DFL round (pjit, stacked node dim over 'data') matches the
   single-device reference bit-for-bit-ish;
 * the sparse engine (make_round_fn(engine="sparse")) matches the dense
   engine for plain DFL, for stochastic losses (unified RNG folding), and
   for C-DFL (shared CHOCO-G step), with and without the Pallas kernel hot
   path (interpret mode on CPU).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.substrate import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.core import ring, mixing, DFLConfig, init_state, make_round_fn
from repro.optim import sgd

mesh = make_mesh((8,), ("data",))
N = 8
topo = ring(N)
x = jax.random.normal(jax.random.key(0), (N, 4, 33))
params = {"w": x}

# dense reference
dense = mixing.mix_dense(params, topo)["w"]

# sparse ppermute path under shard_map
shifts = topo.shifts()
self_w = float(topo.self_weights[0])
def sparse_fn(p):
    return mixing.mix_ppermute_shifts(p, shifts, self_w, "data")
sharded = shard_map(
    sparse_fn, mesh=mesh,
    in_specs=({"w": P("data")},), out_specs={"w": P("data")})(params)["w"]
err = float(jnp.max(jnp.abs(dense - sharded)))
assert err < 1e-5, f"ppermute vs dense mismatch: {err}"
print("PPERMUTE_OK", err)

# sharded DFL round == unsharded DFL round
def loss_fn(p, b, k=None):
    return jnp.mean((p["w"] - b) ** 2)
cfg = DFLConfig(tau1=2, tau2=3, topology=topo)
opt = sgd(0.1)
st0 = init_state({"w": jnp.zeros((4, 33))}, N, opt, jax.random.key(1))
batches = jax.random.normal(jax.random.key(2), (2, N, 4, 33))
rf = make_round_fn(cfg, loss_fn, opt)
ref_state, ref_m = jax.jit(rf)(st0, batches)

sh = NamedSharding(mesh, P("data"))
st_sharded = st0._replace(
    params={"w": jax.device_put(st0.params["w"], sh)},
    opt_state=jax.tree_util.tree_map(lambda t: t, st0.opt_state))
out_state, out_m = jax.jit(
    rf, in_shardings=(None, NamedSharding(mesh, P(None, "data"))))(
    st_sharded, batches)
err2 = float(jnp.max(jnp.abs(ref_state.params["w"] - out_state.params["w"])))
assert err2 < 1e-5, f"sharded round mismatch: {err2}"
print("SHARDED_ROUND_OK", err2)

# production sparse round (shard_map + ppermute) == dense reference.
from repro.core import make_compressor, sparse_engine_eligible
from repro.core.sharded import make_sharded_round_fn
targets = jnp.linspace(-1, 1, N)[:, None] * jnp.ones((N, 33))
det_batches = jnp.broadcast_to(targets[None], (2, N, 33)) * 1.0
det_batches = det_batches[:, :, None, :] * jnp.ones((2, N, 4, 33))
def det_loss2(p, b, k=None):
    return jnp.mean((p["w"][None] - b) ** 2)
cfg2 = DFLConfig(tau1=2, tau2=3, topology=topo)
st0b = init_state({"w": jnp.zeros((33,))}, N, opt, jax.random.key(5))
ref2, _ = jax.jit(make_round_fn(cfg2, det_loss2, opt))(st0b, det_batches)
sharded_fn = make_sharded_round_fn(cfg2, det_loss2, opt, mesh,
                                   node_axes=("data",))
out2, m2 = jax.jit(sharded_fn)(st0b, det_batches)
err3 = float(jnp.max(jnp.abs(ref2.params["w"] - out2.params["w"])))
assert err3 < 1e-5, f"production sharded round mismatch: {err3}"
assert float(m2["consensus_sq"]) >= 0
print("PROD_SHARDED_OK", err3)

# stochastic loss: the unified RNG folding (per-node key =
# fold_in(step_key, node)) makes dense and sparse draw identical noise.
def noisy_loss(p, b, k=None):
    jitter = 0.05 * jax.random.normal(k, p["w"].shape)
    return jnp.mean((p["w"][None] + jitter[None] - b) ** 2)
assert sparse_engine_eligible(cfg2, mesh, ("data",))
ref_n = init_state({"w": jnp.zeros((33,))}, N, opt, jax.random.key(9))
out_n = ref_n
dense_n = jax.jit(make_round_fn(cfg2, noisy_loss, opt))
sparse_n = jax.jit(make_round_fn(cfg2, noisy_loss, opt, engine="auto",
                                 mesh=mesh, node_axes=("data",)))
for _ in range(2):  # two rounds: exercises the round_idx key folding
    ref_n, mr = dense_n(ref_n, det_batches)
    out_n, ms = sparse_n(out_n, det_batches)
err_rng = float(jnp.max(jnp.abs(ref_n.params["w"] - out_n.params["w"])))
assert err_rng < 1e-5, f"stochastic-loss engine mismatch: {err_rng}"
assert abs(float(mr["loss"]) - float(ms["loss"])) < 1e-5
print("RNG_PARITY_OK", err_rng)

# C-DFL parity: the shared CHOCO-G step (incl. stochastic QSGD keys) agrees
# across engines, plain jnp and Pallas-kernel (interpret) hot paths both.
cfg3 = DFLConfig(tau1=2, tau2=2, topology=topo,
                 compression=make_compressor("qsgd"), gamma=0.5)
st0c = init_state({"w": jnp.zeros((33,))}, N, opt, jax.random.key(7),
                  compressed=True)
ref3, _ = jax.jit(make_round_fn(cfg3, det_loss2, opt))(st0c, det_batches)
out3, m3 = jax.jit(make_round_fn(cfg3, det_loss2, opt, engine="sparse",
                                 mesh=mesh, node_axes=("data",)))(
    st0c, det_batches)
err4 = max(float(jnp.max(jnp.abs(ref3.params["w"] - out3.params["w"]))),
           float(jnp.max(jnp.abs(ref3.hat_params["w"] -
                                 out3.hat_params["w"]))))
assert err4 < 1e-5, f"C-DFL engine mismatch: {err4}"
print("CDFL_PARITY_OK", err4)

out4, _ = jax.jit(make_round_fn(cfg3, det_loss2, opt, engine="sparse",
                                mesh=mesh, node_axes=("data",),
                                use_kernels=True))(st0c, det_batches)
err5 = float(jnp.max(jnp.abs(ref3.params["w"] - out4.params["w"])))
assert err5 < 1e-5, f"kernel hot path mismatch: {err5}"
print("KERNELS_OK", err5)

# TopK C-DFL: dense reference vs the sparse engine's FUSED
# compress-and-move kernel (choco_topk_move) — the kernel-backed TopK is
# bitwise vs the library compressor, so engine parity matches the
# uncompressed case.
cfg4 = DFLConfig(tau1=2, tau2=2, topology=topo,
                 compression=make_compressor("top_k", frac=0.3), gamma=0.5)
st0d = init_state({"w": jnp.zeros((33,))}, N, opt, jax.random.key(11),
                  compressed=True)
ref4, _ = jax.jit(make_round_fn(cfg4, det_loss2, opt))(st0d, det_batches)
out5, _ = jax.jit(make_round_fn(cfg4, det_loss2, opt, engine="sparse",
                                mesh=mesh, node_axes=("data",),
                                use_kernels=True))(st0d, det_batches)
err6 = max(float(jnp.max(jnp.abs(ref4.params["w"] - out5.params["w"]))),
           float(jnp.max(jnp.abs(ref4.hat_params["w"] -
                                 out5.hat_params["w"]))))
assert err6 < 1e-5, f"fused TopK kernel engine mismatch: {err6}"
print("TOPK_KERNELS_OK", err6)
"""


@pytest.mark.slow
def test_multidevice_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PPERMUTE_OK" in out.stdout
    assert "SHARDED_ROUND_OK" in out.stdout
    assert "PROD_SHARDED_OK" in out.stdout
    assert "RNG_PARITY_OK" in out.stdout
    assert "CDFL_PARITY_OK" in out.stdout
    assert "KERNELS_OK" in out.stdout
    assert "TOPK_KERNELS_OK" in out.stdout


TRAIN_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from repro.core.sharded import ENGINE_PARITY_LOSS_ATOL
from repro.launch import train

out = sys.argv[1]
common = ["--arch", "qwen3-1.7b", "--nodes", "4", "--topology", "ring",
          "--tau1", "2", "--tau2", "2", "--superstep", "2", "--rounds", "4",
          "--batch", "1", "--seq", "32", "--log-every", "10"]
hist = {}
for engine in ("sparse", "dense"):
    path = os.path.join(out, engine + ".json")
    train.main(common + ["--engine", engine, "--history-out", path])
    with open(path) as f:
        hist[engine] = json.load(f)
s, d = hist["sparse"], hist["dense"]
assert s["engine"] == "sparse" and d["engine"] == "dense"
# the ring's state is split over all 4 devices, and it is built there:
# the first dispatch adds no compile beyond the ahead-of-time warmup.
assert s["state_devices"] == 4, s["state_devices"]
assert s["compile_count"] == s["compile_count_warmup"] == 1, s
# the parameters are bf16: the losses drift apart after the first gossip
# (see ENGINE_PARITY_LOSS_ATOL).
diff = max(abs(a - b) for a, b in zip(s["loss"], d["loss"]))
assert s["loss"][0] == d["loss"][0], (s["loss"], d["loss"])
assert diff < ENGINE_PARITY_LOSS_ATOL, diff
print("TRAIN_SPARSE_OK", diff)
"""


@pytest.mark.slow
def test_train_cli_sparse_ring_matches_dense(tmp_path):
    """``train.main`` on the sparse engine (one node per device): state on
    every device, zero compiles after warmup, and the dense engine's
    losses (the first round's exactly)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", TRAIN_SCRIPT, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TRAIN_SPARSE_OK" in out.stdout
