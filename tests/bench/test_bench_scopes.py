"""Device time by the program's named scopes: scope paths read from an
optimized module's ``op_name`` metadata (a callee inherits its caller's
scopes), device seconds per scope (every named scope of the module, not
only the engine's), backward by the VJP marker, unscoped busy time, idle
gaps labelled by the innermost ``dfl.*`` host span, and the per-layer
readers of the engine's phases."""
import re

import pytest

from bench import scopes, trace
from bench.program import Program
from bench.spec import Bench
from bench.traffic import generator

PHASE_READERS = ("local_s_per_round", "gossip_s_per_round",
                 "backward_s_per_round", "optimizer_s_per_round",
                 "topk_threshold_s_per_round")


def reader(name):
    return Bench().metric_reader(name)

MS = 1_000_000  # ns

HLO = '''HloModule jit_superstep, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.9 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(superstep)/while/body/dfl.local/vmap(jvp())/add"}
}

%body.2 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(superstep)/while/body/dfl.local/vmap(jvp())/add"}
  %copy.4 = f32[8]{0} copy(%fusion.3)
  %dot.5 = f32[8]{0} dot(%copy.4, %copy.4), metadata={op_name="jit(superstep)/while/body/dfl.local/vmap(transpose(jvp()))/dot_general"}
  %sub.6 = f32[8]{0} subtract(%dot.5, %copy.4), metadata={op_name="jit(superstep)/while/body/dfl.local/dfl.local.update/sub"}
  ROOT %tuple.7 = (s32[], f32[8]{0}) tuple(%p, %sub.6)
}

%cond.8 (c: (s32[], f32[8])) -> pred[] {
  %c = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.10 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.11 = (s32[], f32[8]{0}) while(%x), condition=%cond.8, body=%body.2, metadata={op_name="jit(superstep)/while/body/dfl.local/while"}
  %sort.12 = f32[8]{0} sort(%x), metadata={op_name="jit(superstep)/while/body/dfl.gossip/vmap(jit(_top_k_compress_impl))/compress.threshold/top_k"}
  %fusion.13 = f32[8]{0} fusion(%sort.12), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(superstep)/while/body/dfl.gossip/mix"}
  ROOT %reduce.14 = f32[8]{0} add(%fusion.13, %x), metadata={op_name="jit(superstep)/while/body/reduce_sum"}
}
'''


def test_scope_paths_read_op_names_and_inherit_from_the_caller():
    p = scopes.scope_paths(HLO)
    assert p["fusion.3"] == ("dfl.local",)
    # a layout copy with no metadata takes its while loop's scope
    assert p["copy.4"] == ("dfl.local",)
    assert p["dot.5"] == ("dfl.local", scopes.BACKWARD)
    assert p["sub.6"] == ("dfl.local", "dfl.local.update")
    assert p["sort.12"] == ("dfl.gossip", "compress.threshold")
    assert p["fusion.13"] == ("dfl.gossip",)
    assert p["reduce.14"] == ()
    # an instruction inside a fused computation takes its callers' scopes
    assert set(p["add.9"]) == {"dfl.local"}
    assert scopes.phase(p["dot.5"]) == "dfl.local/backward"
    assert scopes.phase(p["sub.6"]) == "dfl.local.update"
    assert scopes.phase(p["reduce.14"]) == "unscoped"


def _events():
    ops = {"/device:TPU:0": [
        ("while.11", 0 * MS, 50 * MS),      # holds the four below
        ("fusion.3", 0 * MS, 10 * MS),
        ("copy.4", 10 * MS, 15 * MS),
        ("dot.5", 15 * MS, 40 * MS),
        ("sub.6", 45 * MS, 50 * MS),
        ("%sort.12 = f32[8]{0} sort(%x)", 60 * MS, 80 * MS),
        ("fusion.13", 80 * MS, 85 * MS),
        ("reduce.14", 85 * MS, 95 * MS),
    ]}
    spans = [("bench.window", 0, 100 * MS),
             ("bench.wait", 48 * MS, 62 * MS),
             ("dfl.dispatch", 49 * MS, 90 * MS),
             ("dfl.metrics.flush", 51 * MS, 59 * MS),
             ("dfl.prefetch.build", 50 * MS, 52 * MS)]
    return ops, spans


def test_reduce_scopes_splits_busy_time_by_phase():
    ops, spans = _events()
    s = scopes.reduce_scopes(ops, spans, scopes.scope_paths(HLO))
    base = trace.reduce_events(ops, spans)
    assert s["scopes"]["dfl.local"] == pytest.approx(0.050)
    assert s["scopes"]["dfl.local.update"] == pytest.approx(0.005)
    assert s["scopes"]["dfl.gossip"] == pytest.approx(0.025)
    assert s["scopes"]["compress.threshold"] == pytest.approx(0.020)
    assert s["backward_s"] == pytest.approx(0.025)
    assert s["unscoped_s"] == pytest.approx(0.010)
    # local + gossip + unscoped is the busy time
    assert (s["scopes"]["dfl.local"] + s["scopes"]["dfl.gossip"]
            + s["unscoped_s"]) == pytest.approx(base["busy_s"])
    assert s["scope_ops"]["dfl.local copy"] == pytest.approx(0.005)
    assert s["scope_ops"]["dfl.local/backward dot"] == pytest.approx(0.025)
    assert s["scope_ops"]["compress.threshold sort"] == pytest.approx(0.020)
    assert "while" not in " ".join(s["scope_ops"])
    # gaps: [50,60] lies inside dfl.dispatch, of which the flush and the
    # build cover only part; [95,100] lies in no dfl span
    assert s["idle_by_program_span"] == pytest.approx(
        {"dfl.dispatch": 0.010, "dfl.none": 0.005})
    table = scopes.tables(s, base["busy_s"])
    assert "dfl.local/backward dot" in table and "dfl.dispatch" in table


def test_the_innermost_of_equally_covering_spans_labels_a_gap():
    spans = [("dfl.dispatch", 0, 100), ("dfl.prefetch.take", 10, 40)]
    assert scopes.covering_span(spans, 20, 30) == "dfl.prefetch.take"
    assert scopes.covering_span(spans, 30, 60) == "dfl.dispatch"
    assert scopes.covering_span(spans, 200, 300) == scopes.NO_SPAN


def test_a_module_without_scopes_reads_none_never_zero():
    ops, spans = _events()
    bare = "\n".join(line.split(", metadata=")[0] if "metadata" in line
                     else line for line in HLO.splitlines())
    paths = scopes.scope_paths(bare)
    assert not any(paths.values())
    s = scopes.reduce_scopes(ops, spans, paths)
    assert s["scopes"] is None and s["backward_s"] is None
    assert s["unscoped_s"] is None
    assert {reader(n)(dict(s, rounds=4)) for n in PHASE_READERS} == {None}
    assert scopes.reduce_scopes({}, spans, paths) == {}
    assert scopes.reduce_scopes(ops, spans[1:], paths) == {}


@pytest.mark.parametrize("name,want", [
    ("local_s_per_round", 0.050),
    ("gossip_s_per_round", 0.025),
    ("backward_s_per_round", 0.025),
    ("optimizer_s_per_round", 0.005),
    ("topk_threshold_s_per_round", 0.020),
])
def test_per_round_reads_each_phase(name, want):
    # each phase's per-layer metric, read by its own file under
    # bench/metrics from the summary a traced run builds
    ops, spans = _events()
    s = scopes.reduce_scopes(ops, spans, scopes.scope_paths(HLO))
    read = reader(name)
    assert read(dict(s, rounds=2)) == pytest.approx(want / 2)
    assert read(dict(s, rounds=0)) is None
    assert read({"rounds": 2}) is None


def test_every_named_scope_is_reported_not_only_the_engines():
    # a scope no file of the benchmark names (a later program's
    # ``moe.experts``) is reported by name; argument names (an ``op_name``
    # with no path) and einsum specs are no scopes
    hlo = HLO.replace("dfl.local/dfl.local.update/sub",
                      "dfl.local/moe.experts/sub").replace(
        "  ROOT %reduce.14", "  %copy.15 = f32[8]{0} copy(%x), metadata="
        "{op_name=\"state.params[\\'embed\\']\"}\n"
        "  %dot.16 = f32[8]{0} dot(%x, %x), metadata={op_name=\"jit("
        "superstep)/while/body/dfl.local/...d,df->...f/dot_general\"}\n"
        "  ROOT %reduce.14")
    p = scopes.scope_paths(hlo)
    assert p["sub.6"] == ("dfl.local", "moe.experts")
    assert p["copy.15"] == () and p["dot.16"] == ("dfl.local",)
    assert scopes.phase(p["sub.6"]) == "moe.experts"
    ops, spans = _events()
    s = scopes.reduce_scopes(ops, spans, p)
    assert set(s["scopes"]) == {"dfl.local", "dfl.gossip",
                                "compress.threshold", "moe.experts"}
    assert s["scopes"]["moe.experts"] == pytest.approx(0.005)
    assert s["scopes"]["dfl.local"] == pytest.approx(0.050)
    assert scopes.per_round(dict(s, rounds=2), "moe.experts") == \
        pytest.approx(0.0025)
    assert reader("optimizer_s_per_round")(dict(s, rounds=2)) is None


def test_two_devices_average_their_phase_time():
    ops, spans = _events()
    ops["/device:TPU:1"] = [("dot.5", 0, 10 * MS)]
    s = scopes.reduce_scopes(ops, spans, scopes.scope_paths(HLO))
    assert s["backward_s"] == pytest.approx((0.025 + 0.010) / 2)


@pytest.fixture
def fresh_compile():
    # JAX's compile-cache key leaves metadata out: where another test turned
    # the persistent cache on, an entry an older program wrote (without the
    # scopes) would be loaded in place of this compile
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("cell", ["tiny-qwen.dfl", "tiny-mamba.dfl",
                                  "tiny-qwen.topk"])
def test_the_compiled_superstep_names_every_phase(tiny_bench, fresh_compile,
                                                  cell):
    # the superstep the window drives, compiled on the CPU: its module
    # carries the engine's scopes, backward under dfl.local (remat
    # recomputation included), and copies XLA added take a phase too
    c = tiny_bench.cell(cell)
    config = tiny_bench.config(c["config"])
    traffic = tiny_bench.traffic(c["traffic"])
    program = Program(config, traffic)
    pool = generator.make_pool(traffic, config["model"]["vocab_size"], 3)
    program.warm(program.build_state(3), program.batches(pool[0]))
    hlo = program.compiled.as_text()
    paths = scopes.scope_paths(hlo)
    phases = {scopes.phase(m) for m in paths.values()}
    want = {"dfl.local", "dfl.local/backward", "dfl.local.update",
            "dfl.gossip"}
    if traffic["compressor"]:
        want.add("compress.threshold")
    assert want <= phases
    remat = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
             if "dfl.local/" in n and "rematted_computation" in n]
    assert remat and all(scopes.BACKWARD in n for n in remat)
    copies = [m for n, m in paths.items() if n.startswith("copy")]
    assert copies and sum(bool(m) for m in copies) > len(copies) / 2
