"""Compiled-artifact audits: what the lint cannot see, read off the HLO.

Four invariants live only in the compiled executable, so no source
check can protect them; each is asserted directly against the lowered /
optimized module of the production superstep
(``RoundExecutor.lower_superstep``):

* **donation** — every ``DFLState`` leaf of the superstep carry must be
  input-output aliased (``input_output_alias`` on the ``HloModule``
  header). A dropped ``donate_argnums`` (the PR-3 regression class)
  silently doubles peak state memory; XLA only warns in logs.
* **recompile** — lowering the superstep at two different trajectory
  values must produce byte-identical HLO. A baked tau constant (someone
  adding ``static_argnums`` or a host ``int()``) shows up as a
  fingerprint mismatch — the PR-3/PR-4 zero-recompile guarantee,
  checked without timing anything.
* **collective-matching** — the sparse engine's ``collective-permute``
  ``source_target_pairs`` in the OPTIMIZED module must equal the pair
  sets implied by ``Topology.shifts()`` — wireless/wire-cost accounting
  (``round_wire_bits``) prices shifts; if XLA ships different pairs the
  accounting is fiction. Parsed via ``launch.hloanalysis
  .collective_sites`` (fusion- and loop-aware, never silently drops).
* **telemetry-neutrality** — the ``repro.obs``-instrumented superstep
  must lower to HLO byte-identical to the uninstrumented one. Telemetry
  hooks are host-side Python at trace/dispatch time; if one ever touches
  a traced value (a ``jax.debug.print``, a host coercion, an inserted
  callback) the instrumented graph diverges and this audit catches it —
  the zero-syncs / zero-recompiles-on-the-round-path contract, enforced
  rather than hoped.

``run_production_audits()`` builds a real 8-node ring sparse superstep
(needs 8 devices — ``python -m repro.analysis audit`` forces 8 host
devices; tests do the same in a subprocess) and runs all four, plus two
participation variants on the widened ``[K, 2+N+E]`` executor:
**participation-recompile** (all-ones vs crash vs sporadic mask
trajectories share one fingerprint — masks are schedule data, never
trace constants) and **participation-collectives** (the masked
executable still ships the full shift pair set — masks gate mixing
weights, not collectives); plus two overlap variants on the pipelined
(``overlap="pipeline"``) executor: **overlap-recompile** (the
double-buffered superstep keeps one fingerprint across trajectories —
the in-flight carry must not bake a tau into the trace) and
**overlap-collectives** (the pipelined executable, drain included,
still ships exactly ``Topology.shifts()`` — overlap moves the exchange
one round later, never onto different wires); plus one batched-engine
variant: **cohort-recompile** (lowering the ``[K, 2+2C+E]`` cohort rows
at the identity cohort and at two distinct ``CohortSampler`` draws
shares one fingerprint — sampled cohort ids are schedule data, so a
mega-scale run never recompiles across draws). The individual
``audit_*`` functions are pure text analysis, testable on synthetic
HLO and deliberately-broken fixtures.
"""
from __future__ import annotations

import dataclasses
import hashlib
import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "AuditResult",
    "parse_input_output_aliases",
    "audit_donation",
    "hlo_fingerprint",
    "audit_recompile",
    "expected_shift_pairs",
    "audit_collective_matching",
    "audit_telemetry_neutrality",
    "build_audit_executor",
    "build_cohort_audit_executor",
    "run_production_audits",
]


@dataclasses.dataclass
class AuditResult:
    name: str
    ok: bool
    detail: str
    data: dict = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"name": self.name, "ok": self.ok, "detail": self.detail,
                "data": self.data}


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------

_ALIAS_ENTRY_RE = re.compile(r"\{\s*([0-9,\s]*)\}\s*:\s*\((\d+)")


def _balanced_block(text: str, start: int) -> str:
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "{":
            depth += 1
        elif text[i] == "}":
            depth -= 1
            if depth == 0:
                return text[start:i + 1]
    return text[start:]


def parse_input_output_aliases(hlo_text: str) -> Dict[Tuple[int, ...], int]:
    """``{output_tuple_index: parameter_number}`` from the module header's
    ``input_output_alias={ {0}: (0, {}, may-alias), ... }`` annotation.
    Empty dict when the module declares no aliasing (= nothing donated)."""
    key = "input_output_alias="
    pos = hlo_text.find(key)
    if pos < 0:
        return {}
    block = _balanced_block(hlo_text, pos + len(key))
    out: Dict[Tuple[int, ...], int] = {}
    for m in _ALIAS_ENTRY_RE.finditer(block):
        idx = tuple(int(x) for x in m.group(1).replace(" ", "").split(",")
                    if x != "")
        out[idx] = int(m.group(2))
    return out


def audit_donation(compiled_text: str, leaf_names: Sequence[str],
                   name: str = "donation") -> AuditResult:
    """Every one of the first ``len(leaf_names)`` parameters (the
    flattened donated carry, in tree-flatten order) must appear as an
    aliased input in the compiled module."""
    aliases = parse_input_output_aliases(compiled_text)
    donated = set(aliases.values())
    missing = [f"param {i} ({n})" for i, n in enumerate(leaf_names)
               if i not in donated]
    data = {"aliases": {str(k): v for k, v in aliases.items()},
            "expected_params": len(leaf_names), "missing": missing}
    if missing:
        return AuditResult(name, False,
                           f"carry leaves NOT donated: {missing} — check "
                           "donate_argnums on the superstep jit", data)
    return AuditResult(
        name, True,
        f"all {len(leaf_names)} state leaves input-output aliased", data)


# ---------------------------------------------------------------------------
# recompile fingerprints
# ---------------------------------------------------------------------------


def hlo_fingerprint(hlo_text: str) -> str:
    return hashlib.sha256(hlo_text.encode()).hexdigest()[:16]


def audit_recompile(lowered_texts: Sequence[str],
                    labels: Optional[Sequence[str]] = None,
                    name: str = "recompile") -> AuditResult:
    """All lowerings (same shapes, different schedule VALUES) must be
    byte-identical: a difference means a tau reached the trace as a
    constant (static_argnums / host int()) and every re-plan recompiles."""
    labels = list(labels or range(len(lowered_texts)))
    fps = [hlo_fingerprint(t) for t in lowered_texts]
    data = {"fingerprints": dict(zip(map(str, labels), fps))}
    if len(set(fps)) != 1:
        return AuditResult(
            name, False,
            f"HLO fingerprints differ across schedule values {data} — a "
            "(tau1, tau2) constant is baked into the executable", data)
    return AuditResult(
        name, True,
        f"{len(fps)} lowerings share one fingerprint {fps[0]}", data)


# ---------------------------------------------------------------------------
# collective matching
# ---------------------------------------------------------------------------


def expected_shift_pairs(topology) -> Dict[int, frozenset]:
    """shift s -> the ppermute pair set {(src, (src+s) % N)} it lowers to
    (see mixing.mix_ppermute_shifts / ShardedSubstrate.mix)."""
    n = topology.num_nodes
    return {
        int(s): frozenset((src, (src + int(s)) % n) for src in range(n))
        for s, _ in topology.shifts()
    }


def audit_collective_matching(optimized_text: str, topology,
                              name: str = "collective-matching"
                              ) -> AuditResult:
    """The optimized module's collective-permute pair sets must be
    exactly the topology's shift pair sets — no missing shift (a node
    silently not gossiping) and no extra/wrong permute (traffic the wire
    accounting never priced)."""
    from repro.launch.hloanalysis import collective_sites

    # warn=False: trip counts are irrelevant to pair matching, and
    # optimized modules routinely carry unannotated control-flow loops.
    sites = [s for s in collective_sites(optimized_text, warn=False)
             if s.opcode == "collective-permute"]
    observed = {frozenset(s.pairs) for s in sites if s.pairs}
    expected = set(expected_shift_pairs(topology).values())
    data = {
        "num_permutes": len(sites),
        "observed": sorted(sorted(p) for p in observed),
        "expected": sorted(sorted(p) for p in expected),
    }
    if not expected:
        return AuditResult(name, not observed,
                           "topology has no shifts; module must have no "
                           "permutes", data)
    missing = expected - observed
    extra = observed - expected
    if missing or extra:
        return AuditResult(
            name, False,
            f"permute pairs != Topology.shifts(): missing shifts "
            f"{sorted(sorted(p) for p in missing)}, unexpected "
            f"{sorted(sorted(p) for p in extra)}", data)
    return AuditResult(
        name, True,
        f"{len(sites)} collective-permutes, pair sets == shifts("
        f"{topology.name})", data)


# ---------------------------------------------------------------------------
# telemetry neutrality
# ---------------------------------------------------------------------------


def audit_telemetry_neutrality(bare_text: str, instrumented_text: str,
                               name: str = "telemetry-neutrality"
                               ) -> AuditResult:
    """The telemetry-instrumented superstep must lower to HLO
    byte-identical to the bare one: observability may never add a host
    sync, a traced op, or a recompile to the round path. The caller
    lowers the SAME function with and without a live ``repro.obs``
    sink (the instrumented trace really runs its hooks — see
    ``RoundExecutor.lower_superstep``); any graph divergence lands here
    as a fingerprint mismatch."""
    fp_bare = hlo_fingerprint(bare_text)
    fp_inst = hlo_fingerprint(instrumented_text)
    data = {"fingerprints": {"bare": fp_bare, "instrumented": fp_inst}}
    if fp_bare != fp_inst:
        return AuditResult(
            name, False,
            "telemetry instrumentation CHANGED the superstep HLO "
            f"({fp_bare} != {fp_inst}) — a hook leaked a traced op or "
            "host sync into the round path", data)
    return AuditResult(
        name, True,
        f"instrumented lowering fingerprint-identical to bare ({fp_bare})",
        data)


# ---------------------------------------------------------------------------
# the production artifact
# ---------------------------------------------------------------------------


def build_audit_executor(num_nodes: int = 8, *, tau1_max: int = 3,
                         tau2_max: int = 2, rounds: int = 2, dim: int = 33,
                         telemetry=None, participation: bool = False,
                         overlap: str = "none"):
    """A small but REAL sparse-engine superstep: ring(N) topology, node
    axis manual over an N-device mesh, dynamic taus, donated carry — the
    exact executable class ``launch.train`` dispatches. Returns
    ``(executor, state, batches, topology)`` ready for
    ``executor.lower_superstep``. Needs ``num_nodes`` devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import DFLConfig, init_state, make_round_fn  # noqa: F401
    from repro.core.executor import RoundExecutor, stack_round_batches
    from repro.core.substrate import make_mesh
    from repro.core.topology import ring
    from repro.optim import sgd

    if len(jax.devices()) < num_nodes:
        raise RuntimeError(
            f"audit superstep needs {num_nodes} devices, have "
            f"{len(jax.devices())} — run via `python -m repro.analysis "
            "audit` (it forces host devices) or set XLA_FLAGS")
    mesh = make_mesh((num_nodes,), ("data",))
    topo = ring(num_nodes)
    cfg = DFLConfig(tau1=tau1_max, tau2=tau2_max, topology=topo)
    opt = sgd(0.1)

    def loss_fn(p, b, k=None):
        return jnp.mean((p["w"][None] - b) ** 2)

    ex = RoundExecutor(cfg, loss_fn, opt, engine="sparse", mesh=mesh,
                       node_axes=("data",), dynamic=True, donate=True,
                       telemetry=telemetry, participation=participation,
                       overlap=overlap)
    state = init_state({"w": jnp.zeros((dim,))}, num_nodes, opt,
                       jax.random.key(0))
    sh = NamedSharding(mesh, P("data"))
    state = state._replace(
        params=jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), state.params))
    key = jax.random.key(1)
    per_round = [jax.random.normal(jax.random.fold_in(key, r),
                                   (tau1_max, num_nodes, 4, dim))
                 for r in range(rounds)]
    batches = stack_round_batches(per_round, tau1_max)
    return ex, state, batches, topo


def build_cohort_audit_executor(population: int = 32, cohort: int = 8, *,
                                tau1_max: int = 3, tau2_max: int = 2,
                                rounds: int = 2, dim: int = 33):
    """A small but REAL batched-engine superstep: ring(C) cohort topology
    over a ``population``-node virtual state stack, dynamic taus, cohort
    ids as schedule data — the executable class ``launch.train
    --virtual-nodes`` dispatches. Single-device (the whole point of the
    batched engine). Returns ``(executor, state, batches, topology)``."""
    import jax
    import jax.numpy as jnp

    from repro.core import DFLConfig, init_state
    from repro.core.executor import RoundExecutor, stack_round_batches
    from repro.core.topology import ring
    from repro.optim import sgd

    topo = ring(cohort)
    cfg = DFLConfig(tau1=tau1_max, tau2=tau2_max, topology=topo)
    opt = sgd(0.1)

    def loss_fn(p, b, k=None):
        return jnp.mean((p["w"][None] - b) ** 2)

    ex = RoundExecutor(cfg, loss_fn, opt, engine="batched",
                       population=population, dynamic=True, donate=True)
    state = init_state({"w": jnp.zeros((dim,))}, population, opt,
                       jax.random.key(0))
    key = jax.random.key(1)
    per_round = [jax.random.normal(jax.random.fold_in(key, r),
                                   (tau1_max, cohort, 4, dim))
                 for r in range(rounds)]
    batches = stack_round_batches(per_round, tau1_max)
    return ex, state, batches, topo


def run_production_audits(num_nodes: int = 8) -> List[AuditResult]:
    """Build the production sparse superstep (plus its participation and
    pipelined-overlap variants) and run the full audit suite."""
    import jax

    from repro.obs import Telemetry

    ex, state, batches, topo = build_audit_executor(num_nodes)
    leaf_names = [str(p) for p, _ in
                  jax.tree_util.tree_flatten_with_path(state)[0]]
    taus_a = [[1, 1]] * 2
    taus_b = [[3, 0], [2, 2]]
    low_a = ex.lower_superstep(state, batches, taus_a)
    low_b = ex.lower_superstep(state, batches, taus_b)
    compiled_text = low_a.compile().as_text()
    # identical build with a LIVE telemetry sink: its trace-time hooks
    # run during this lowering (same example args as low_a), and the
    # neutrality audit asserts the graph didn't move.
    tel = Telemetry()
    ex_inst, state_i, batches_i, _ = build_audit_executor(
        num_nodes, telemetry=tel)
    low_inst = ex_inst.lower_superstep(state_i, batches_i, taus_a)
    assert any(e["type"] == "compile" for e in tel.events), (
        "instrumented audit lowering never ran its telemetry hooks — "
        "the neutrality comparison would be vacuous")

    # Participation: masked trajectories are schedule DATA on the widened
    # [K, 2+N+E] rows — lowering an all-ones trajectory and two distinct
    # fault patterns must produce one fingerprint (masks never reach the
    # trace as constants), and the masked executable must still ship the
    # full shift pair set (masks gate mixing WEIGHTS, not collectives —
    # dropping a ppermute per masked edge would recompile per pattern).
    import numpy as np

    from repro.faults import FaultPlan, NodeCrash, SporadicParticipation

    ex_p, state_p, batches_p, _ = build_audit_executor(
        num_nodes, participation=True)
    taus = np.array([[1, 1], [2, 1]], np.int32)
    all_on = np.concatenate(
        [taus, np.ones((2, ex_p.row_width - 2), np.int32)], axis=1)
    crash = FaultPlan(topo, (NodeCrash(3, 0, 8),), seed=0)
    sporadic = FaultPlan(
        topo, (SporadicParticipation(0.6, 0.5, 0, 8),), seed=7)
    low_on = ex_p.lower_superstep(state_p, batches_p, all_on)
    low_crash = ex_p.lower_superstep(state_p, batches_p,
                                     crash.mask_trajectory(taus))
    low_spor = ex_p.lower_superstep(state_p, batches_p,
                                    sporadic.mask_trajectory(taus))

    # Overlap: the pipelined superstep is still schedule-as-data — one
    # fingerprint across trajectories (the double-buffer carry must not
    # smuggle a tau into the trace as a constant) — and its executable
    # still ships exactly the topology's shift pairs (pipelining moves
    # the exchange one round LATER, it must not move it onto different
    # wires or drop the drain's final exchange).
    ex_o, state_o, batches_o, _ = build_audit_executor(
        num_nodes, overlap="pipeline")
    low_oa = ex_o.lower_superstep(state_o, batches_o, taus_a)
    low_ob = ex_o.lower_superstep(state_o, batches_o, taus_b)

    # Cohort sampling: on the batched engine the [K, 2+2C+E] rows carry
    # the sampled cohort IDS as schedule data — lowering the identity
    # cohort and two distinct CohortSampler draws must share one
    # fingerprint (a baked id constant would recompile on every draw,
    # destroying the mega-scale zero-recompile property).
    from repro.faults import CohortSampler

    ex_c, state_c, batches_c, topo_c = build_cohort_audit_executor()
    pop = ex_c.population
    sampler_a = CohortSampler(population=pop, cohort=topo_c.num_nodes,
                              seed=3)
    sampler_b = CohortSampler(population=pop, cohort=topo_c.num_nodes,
                              seed=11)
    identity = np.array([[1, 1], [2, 1]], np.int32)
    low_ca = ex_c.lower_superstep(
        state_c, batches_c, ex_c._check_trajectory(identity, 2))
    low_cb = ex_c.lower_superstep(
        state_c, batches_c,
        sampler_a.cohort_trajectory(identity, num_edges=topo_c.num_edges))
    low_cc = ex_c.lower_superstep(
        state_c, batches_c,
        sampler_b.cohort_trajectory(identity, round0=5,
                                    num_edges=topo_c.num_edges))

    return [
        audit_donation(compiled_text, leaf_names),
        audit_recompile([low_a.as_text(), low_b.as_text()],
                        labels=["taus=[[1,1],[1,1]]", "taus=[[3,0],[2,2]]"]),
        audit_collective_matching(compiled_text, topo),
        audit_telemetry_neutrality(low_a.as_text(), low_inst.as_text()),
        audit_recompile(
            [low_on.as_text(), low_crash.as_text(), low_spor.as_text()],
            labels=["all-ones", "crash(node=3)", "sporadic(p=0.6/0.5)"],
            name="participation-recompile"),
        audit_collective_matching(low_crash.compile().as_text(), topo,
                                  name="participation-collectives"),
        audit_recompile([low_oa.as_text(), low_ob.as_text()],
                        labels=["taus=[[1,1],[1,1]]", "taus=[[3,0],[2,2]]"],
                        name="overlap-recompile"),
        audit_collective_matching(low_oa.compile().as_text(), topo,
                                  name="overlap-collectives"),
        audit_recompile(
            [low_ca.as_text(), low_cb.as_text(), low_cc.as_text()],
            labels=["identity-cohort", "sampler(seed=3)@r0",
                    "sampler(seed=11)@r5"],
            name="cohort-recompile"),
    ]
