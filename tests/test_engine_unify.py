"""Unit tests of the dense/sparse engine unification layer.

Covers the pieces the multi-device subprocess test can't check cheaply:
the wire-cost accounting helper, the engine-eligibility predicate (and its
agreement with ``Topology.shifts()``), the JAX version-compat shims, and
the substrate metric/key parity on the dense side.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import (DFLConfig, DenseSubstrate, consensus_distance,
                        disconnected, fully_connected, make_compressor,
                        ring, round_wire_bits, sparse_engine_eligible, star,
                        torus)
from repro.core import mixing as M
from repro.core import substrate as sub_lib
from repro.core.substrate import make_mesh
from repro.core.compression import Identity, tree_wire_bits


class FakeMesh:
    """Just enough of a Mesh for the eligibility predicate."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


# ---------------------------------------------------------------------------
# Wire-cost accounting (one helper, both engines)
# ---------------------------------------------------------------------------


def test_gossip_copies_dense_vs_sparse():
    topo = ring(8)
    assert M.gossip_copies_per_step(topo, "sparse") == 2      # deg
    assert M.gossip_copies_per_step(topo, "dense") == 7       # N-1 all-gather
    assert M.gossip_copies_per_step(topo, "auto") == 2        # ring -> sparse
    hub = star(8)
    assert M.gossip_copies_per_step(hub, "sparse") == 7       # hub degree
    assert M.gossip_copies_per_step(hub, "auto") == 7         # not circulant
    with pytest.raises(ValueError):
        M.gossip_copies_per_step(topo, "einsum")


def test_mixing_bytes_per_step_uses_helper():
    topo = torus(2, 4)
    pb = 1000
    assert (M.mixing_bytes_per_step(topo, pb, sparse=True)
            == M.gossip_copies_per_step(topo, "sparse") * pb)
    assert (M.mixing_bytes_per_step(topo, pb, sparse=False)
            == (topo.num_nodes - 1) * pb)


def test_round_wire_bits_engine_parameterized():
    params = {"w": jnp.zeros((100,)), "b": jnp.zeros((10,))}
    cfg = DFLConfig(tau1=2, tau2=3, topology=ring(8))
    full = tree_wire_bits(Identity(), params)
    assert round_wire_bits(cfg, params, engine="sparse") == full * 2 * 3
    assert round_wire_bits(cfg, params, engine="dense") == full * 7 * 3
    # compressed accounting still scales by the engine's copy count.
    ccfg = DFLConfig(tau1=2, tau2=3, topology=ring(8),
                     compression=make_compressor("qsgd"))
    assert (round_wire_bits(ccfg, params, engine="dense")
            > round_wire_bits(ccfg, params, engine="sparse"))


# ---------------------------------------------------------------------------
# Engine eligibility: predicate and shifts() agree
# ---------------------------------------------------------------------------


def test_shift_structured_agrees_with_shifts():
    """is_shift_structured() is THE eligibility predicate: wherever it says
    True, the sparse engine must accept (non-empty shifts, or C = I)."""
    for topo in (ring(6), torus(2, 3), fully_connected(5), disconnected(6),
                 star(6)):
        structured = topo.is_shift_structured()
        if structured and topo.max_degree > 0:
            assert topo.shifts(), topo.name
        if not structured:
            assert not topo.shifts() or topo.max_degree == 0, topo.name
    assert disconnected(6).is_shift_structured()      # C = I: zero shifts OK
    assert disconnected(6).shifts() == []
    assert not star(6).is_shift_structured()          # hub: not circulant


def test_sharded_substrate_accepts_degenerate_no_edge_topology():
    # The predicate and the engine must agree on C = I: constructing the
    # substrate (which asserts eligibility) must succeed, with no shifts.
    s = sub_lib.ShardedSubstrate(disconnected(4), ("data",))
    assert s.shifts == [] and s.self_weight == 1.0
    with pytest.raises(AssertionError):
        sub_lib.ShardedSubstrate(star(4), ("data",))


def test_sharded_round_fn_rejects_mismatched_mesh():
    """Forcing engine='sparse' bypasses auto-eligibility, so the engine
    itself must reject a mesh whose node axes don't enumerate all nodes
    (it would silently drop every node beyond the axis size)."""
    from repro.core import init_state, make_round_fn
    from repro.optim import sgd

    mesh = make_mesh((1,), ("data",))
    cfg = DFLConfig(tau1=1, tau2=1, topology=ring(4))
    with pytest.raises(AssertionError, match="4 nodes"):
        make_round_fn(cfg, lambda p, b, k: 0.0, sgd(0.1),
                      engine="sparse", mesh=mesh, node_axes=("data",))


def test_sparse_engine_eligibility_rules():
    cfg = DFLConfig(tau1=1, tau2=1, topology=ring(4))
    assert sparse_engine_eligible(cfg, FakeMesh({"data": 4}), ("data",))
    # node axes must enumerate all N nodes
    assert not sparse_engine_eligible(cfg, FakeMesh({"data": 2}), ("data",))
    assert not sparse_engine_eligible(cfg, None, ("data",))
    # non-circulant topology -> dense
    scfg = DFLConfig(tau1=1, tau2=1, topology=star(4))
    assert not sparse_engine_eligible(scfg, FakeMesh({"data": 4}), ("data",))
    # dense-only features -> dense
    pcfg = DFLConfig(tau1=1, tau2=2, topology=ring(4),
                     mixing_impl="dense_power")
    assert not sparse_engine_eligible(pcfg, FakeMesh({"data": 4}), ("data",))
    # single node -> dense
    ocfg = DFLConfig(tau1=1, tau2=1, topology=fully_connected(1))
    assert not sparse_engine_eligible(ocfg, FakeMesh({"data": 1}), ("data",))
    # >1-sized auto axes need a JAX whose partial-manual shard_map works
    mesh_tp = FakeMesh({"data": 4, "model": 2})
    assert (sparse_engine_eligible(cfg, mesh_tp, ("data",))
            == sub_lib.supports_partial_auto())


# ---------------------------------------------------------------------------
# substrate shard_map / axis_size wrappers
# ---------------------------------------------------------------------------


def test_compat_shard_map_and_axis_size_single_device():
    mesh = make_mesh((1,), ("data",))

    def body(x):
        return x * sub_lib.axis_size("data") + jax.lax.axis_index("data")

    out = sub_lib.shard_map(body, mesh, (P("data"),), P("data"))(
        jnp.ones((1, 3)))
    np.testing.assert_allclose(np.asarray(out), np.ones((1, 3)))


def test_mix_ppermute_empty_shifts_is_identity():
    mesh = make_mesh((1,), ("data",))
    x = {"w": jnp.arange(4.0)[None]}
    out = sub_lib.shard_map(
        lambda p: M.mix_ppermute_shifts(p, [], 1.0, "data"),
        mesh, (P("data"),), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(x["w"]))


# ---------------------------------------------------------------------------
# Dense substrate: shared formulas match the historical reference ones
# ---------------------------------------------------------------------------


def test_dense_substrate_consensus_matches_reference():
    params = {"w": jax.random.normal(jax.random.key(0), (6, 11)),
              "b": jax.random.normal(jax.random.key(1), (6, 3, 2))}
    sub = DenseSubstrate(ring(6))
    got = float(sub.consensus_sq(params))
    want = float(consensus_distance(params))
    assert abs(got - want) < 1e-4 * max(1.0, abs(want))


def test_dense_substrate_node_keys_fold_discipline():
    sub = DenseSubstrate(ring(4))
    key = jax.random.key(3)
    keys = sub.node_keys(key)
    for i in range(4):
        np.testing.assert_array_equal(
            jax.random.key_data(keys[i]),
            jax.random.key_data(jax.random.fold_in(key, i)))
