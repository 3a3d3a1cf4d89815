"""The PR-4 sparse-engine safety guard, both halves, tested directly.

The sparse engine's node axes are shard_map-manual, but its non-node
(auto/GSPMD) axes run unconstrained: a ``constrain`` passed on a mesh
with a >1-sized auto axis would be silently dropped — re-opening the
scan-carry all-gather blowup the constraint exists to prevent. The
guard therefore has two cooperating halves:

* ``core.sharded.make_sharded_round_fn`` RAISES ``NotImplementedError``
  when given a constrain on such a mesh (loud, not silent), and
* ``launch.steps.select_engine("auto", ...)`` routes such meshes to the
  dense engine so production auto-selection never steers into the raise.

In-process tests use ``jax.sharding.AbstractMesh`` (no devices needed);
the concrete-mesh end is covered in a subprocess with 8 fake devices.
"""
import os
import subprocess
import sys

import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.core import DFLConfig, make_round_fn, ring
from repro.core.dfl import sparse_engine_eligible
from repro.launch.steps import select_engine
from repro.optim import sgd


def _loss(p, b, k=None):
    import jax.numpy as jnp

    return jnp.mean((p["w"][None] - b) ** 2)


def _mesh(*axes):
    names, sizes = zip(*axes)
    return AbstractMesh(sizes, names,
                        axis_types=(AxisType.Auto,) * len(names))


def test_select_engine_routes_partial_auto_mesh_dense():
    # 4 nodes on "data", a 2-sized "model" auto axis: eligible-looking,
    # but auto must pick dense (the constrain would be dropped in sparse).
    dcfg = DFLConfig(tau1=2, tau2=1, topology=ring(4))
    mesh = _mesh(("data", 4), ("model", 2))
    assert select_engine("auto", dcfg, mesh, "gossip-dp") == "dense"


def test_select_engine_picks_sparse_on_node_only_mesh():
    dcfg = DFLConfig(tau1=2, tau2=1, topology=ring(8))
    mesh = _mesh(("data", 8), ("model", 1))
    assert select_engine("auto", dcfg, mesh, "gossip-dp") == "sparse"
    assert select_engine("auto", dcfg, _mesh(("data", 8)),
                         "gossip-dp") == "sparse"


def test_select_engine_explicit_choice_is_respected():
    dcfg = DFLConfig(tau1=2, tau2=1, topology=ring(4))
    mesh = _mesh(("data", 4), ("model", 2))
    assert select_engine("dense", dcfg, mesh, "gossip-dp") == "dense"
    # explicit "sparse" passes through — the raise in make_sharded_round_fn
    # is then the (loud) guard.
    assert select_engine("sparse", dcfg, mesh, "gossip-dp") == "sparse"


def test_select_engine_dense_for_non_circulant_and_fsdp_modes():
    from repro.core.topology import star

    mesh = _mesh(("data", 8))
    assert select_engine(
        "auto", DFLConfig(tau1=2, tau2=1, topology=star(8)), mesh,
        "gossip-dp") == "dense"
    # gossip-fsdp on a podless mesh has no node axes at all.
    assert select_engine(
        "auto", DFLConfig(tau1=2, tau2=1, topology=ring(8)), mesh,
        "gossip-fsdp") == "dense"


def test_sparse_engine_eligible_accepts_abstract_mesh():
    dcfg = DFLConfig(tau1=2, tau2=1, topology=ring(8))
    assert sparse_engine_eligible(dcfg, _mesh(("data", 8)), ("data",))
    assert not sparse_engine_eligible(dcfg, _mesh(("data", 4)), ("data",))
    assert not sparse_engine_eligible(dcfg, _mesh(("data", 8)), ("nodes",))


GUARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.core.substrate import make_mesh
from repro.core import DFLConfig, make_round_fn, ring
from repro.core.sharded import make_sharded_round_fn
from repro.launch.steps import select_engine
from repro.optim import sgd

def loss(p, b, k=None):
    return jnp.mean((p["w"][None] - b) ** 2)

mesh42 = make_mesh((4, 2), ("data", "model"))
cfg = DFLConfig(tau1=2, tau2=1, topology=ring(4))

# half 1: the sparse builder raises loudly on constrain + >1 auto axis,
# through both the direct and the make_round_fn entry points.
for builder in (
    lambda: make_sharded_round_fn(cfg, loss, sgd(0.1), mesh42,
                                  node_axes=("data",),
                                  constrain=lambda t: t),
    lambda: make_round_fn(cfg, loss, sgd(0.1), constrain=lambda t: t,
                          engine="sparse", mesh=mesh42,
                          node_axes=("data",)),
):
    try:
        builder()
        raise SystemExit("guard did not raise")
    except NotImplementedError as e:
        assert "constrain" in str(e), e
print("GUARD_RAISES_OK")

# without a constrain the same mesh builds fine (auto axes stay GSPMD).
make_sharded_round_fn(cfg, loss, sgd(0.1), mesh42, node_axes=("data",))
print("GUARD_NO_CONSTRAIN_OK")

# half 2: auto-selection on the CONCRETE mesh routes dense, so the
# production path (which always passes a constrain) never hits the raise.
assert select_engine("auto", cfg, mesh42, "gossip-dp") == "dense"
mesh8 = make_mesh((8,), ("data",))
cfg8 = DFLConfig(tau1=2, tau2=1, topology=ring(8))
assert select_engine("auto", cfg8, mesh8, "gossip-dp") == "sparse"
print("GUARD_ROUTES_DENSE_OK")
"""


@pytest.mark.slow
def test_guard_on_concrete_mesh_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", GUARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ["GUARD_RAISES_OK", "GUARD_NO_CONSTRAIN_OK",
                "GUARD_ROUTES_DENSE_OK"]:
        assert tag in out.stdout, out.stdout
