"""Attribute a traced window's device time to the program's named phases,
and its idle gaps to the program's own host spans.

The engine names its phases with ``jax.named_scope`` (``SCOPES``); XLA
keeps the scope path in each instruction's ``op_name`` metadata, so the
compiled module's text maps every device op event (named by its
instruction) to the phases it belongs to. Backward is no scope of its
own: it is the ops under ``dfl.local`` whose ``op_name`` holds JAX's VJP
marker ``transpose(`` (remat recomputation included). The executor opens
``jax.profiler.TraceAnnotation`` host spans named ``dfl.*`` (dispatch,
compile, prefetch build and take, metrics flush) on the profiler's clock.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs one cell as ``bench/run.py`` does (set-up and the first superstep),
then its window twice, untraced and traced, and prints one JSON object:
tokens per second of each window, and the traced window's device seconds
per phase and per round. Tables of the largest (phase, op) pairs and of
the idle gaps by program span go to standard error. No correctness check
runs. A host whose JAX finds no TPU exits 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))]

from bench.trace import (DEVICE_PLANE, OPS_LINE, WINDOW, gaps,  # noqa: E402
                         instruction, op_label, union)

SCOPES = ("dfl.local", "dfl.local.update", "dfl.gossip",
          "compress.threshold")
BACKWARD = "transpose("
PROGRAM_SPAN = "dfl."
NO_SPAN = "dfl.none"

Event = Tuple[str, float, float]

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)"
    r"|\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _marks(op_name: str) -> Tuple[str, ...]:
    """The registered scopes on one ``op_name``, and the VJP marker."""
    parts = set(re.split(r"[/();]", op_name))   # ";" joins merged ops
    found = tuple(s for s in SCOPES if s in parts)
    return found + ((BACKWARD,) if BACKWARD in op_name else ())


def scope_paths(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """Instruction name -> the registered scopes (and ``BACKWARD``) of an
    optimized HLO module. An instruction also takes the scopes of the
    instruction that calls its computation (a loop, call, conditional or
    fusion), recursively: a loop under ``dfl.local`` runs ``dfl.local``
    work, and a layout ``copy`` XLA inserted there carries no metadata."""
    own: Dict[str, Tuple[str, ...]] = {}
    home: Dict[str, str] = {}          # instruction -> its computation
    caller: Dict[str, str] = {}        # computation -> calling instruction
    comp = ""
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m and " = " not in line:
                comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        home[name] = comp
        op = _OP_NAME.search(line)
        own[name] = _marks(op.group(1)) if op else ()
        for called in _CALLED.finditer(line):
            for callee in (called.group(1) or called.group(2)).split(","):
                caller.setdefault(callee.strip().lstrip("%"), name)

    memo: Dict[str, Tuple[str, ...]] = {}

    def inherited(name: str) -> Tuple[str, ...]:
        if name not in memo:
            up = caller.get(home.get(name, ""))
            marks = set(own.get(name, ()))
            if up is not None:         # the call graph of a module is a DAG
                marks |= set(inherited(up))
            memo[name] = tuple(m for m in SCOPES + (BACKWARD,)
                               if m in marks)
        return memo[name]

    return {name: inherited(name) for name in own}


def phase(marks: Sequence[str]) -> str:
    """A (scope, op) table's phase of one op: its innermost registered
    scope, ``/backward`` under the VJP marker, or ``unscoped``."""
    scopes = [s for s in SCOPES if s in marks]
    if not scopes:
        return "unscoped"
    inner = max(scopes, key=len)
    return inner + ("/backward" if BACKWARD in marks else "")


def _length(intervals) -> float:
    return sum(e - s for s, e in union(list(intervals)))


def reduce_scopes(device_ops: Dict[str, List[Event]],
                  host_spans: List[Event],
                  paths: Dict[str, Tuple[str, ...]],
                  kernels: Optional[Dict[str, str]] = None) -> Dict:
    """Device seconds per registered scope (nested scopes count in both),
    ``backward_s``, ``unscoped_s`` (busy time in no registered scope),
    ``scope_ops`` (seconds per (phase, op label)) and
    ``idle_by_program_span``, over the ``bench.window`` span; averaged over
    devices as ``bench.trace.reduce_events`` averages. Empty without a
    window or device ops; ``scopes`` is None when ``paths`` carries no
    registered scope (a module compiled without them). A loop's or call's
    interval counts for its scopes and in no (phase, op) pair."""
    kernels = kernels or {}
    win = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not win or not device_ops:
        return {}
    window = (min(s for s, _ in win), max(e for _, e in win))
    named = any(set(m) & set(SCOPES) for m in paths.values())
    per_scope: Dict[str, float] = {s: 0.0 for s in SCOPES}
    pairs: Dict[str, float] = {}
    backward = unscoped = 0.0
    idle: Dict[str, float] = {}
    spans = [(n, s, e) for n, s, e in host_spans
             if n.startswith(PROGRAM_SPAN)]
    for ops in device_ops.values():
        every, scoped, bwd = [], [], []
        by_scope: Dict[str, list] = {s: [] for s in SCOPES}
        for name, s, e in ops:
            s, e = max(s, window[0]), min(e, window[1])
            if e <= s:
                continue
            every.append((s, e))
            marks = paths.get(instruction(name), ())
            for sc in SCOPES:
                if sc in marks:
                    by_scope[sc].append((s, e))
            if set(marks) & set(SCOPES):
                scoped.append((s, e))
            if "dfl.local" in marks and BACKWARD in marks:
                bwd.append((s, e))
            label = op_label(name, kernels)
            if label is not None:
                key = f"{phase(marks)} {label}"
                pairs[key] = pairs.get(key, 0.0) + (e - s)
        busy = union(every)
        unscoped += sum(e - s for s, e in busy) - _length(scoped)
        backward += _length(bwd)
        for sc, iv in by_scope.items():
            per_scope[sc] += _length(iv)
        for gs, ge in gaps(busy, window):
            label = covering_span(spans, gs, ge)
            idle[label] = idle.get(label, 0.0) + (ge - gs)
    k = 1e-9 / len(device_ops)
    return {
        "scopes": ({sc: v * k for sc, v in per_scope.items()}
                   if named else None),
        "backward_s": backward * k if named else None,
        "unscoped_s": unscoped * k if named else None,
        "scope_ops": {p: v * k for p, v in pairs.items()},
        "idle_by_program_span": {n: v * k for n, v in idle.items()},
    }


def covering_span(spans: List[Event], start: float, end: float) -> str:
    """The ``dfl.*`` span that covers most of [start, end]; among equals
    the innermost (shortest); ``NO_SPAN`` where none overlaps it."""
    best, best_key = NO_SPAN, (0.0, 0.0)
    for n, s, e in spans:
        overlap = min(e, end) - max(s, start)
        key = (overlap, -(e - s))
        if overlap > 0 and key > best_key:
            best, best_key = n, key
    return best


def per_round(summary: Dict, rounds: int) -> Dict[str, Optional[float]]:
    """Device seconds per round of each phase: local, gossip, backward,
    optimizer and TopK threshold; None where the module carries no
    registered scope."""
    scopes = summary.get("scopes")
    if not scopes or not rounds:
        return {k: None for k in ("local_s_per_round", "gossip_s_per_round",
                                  "backward_s_per_round",
                                  "optimizer_s_per_round",
                                  "topk_threshold_s_per_round")}
    return {"local_s_per_round": scopes["dfl.local"] / rounds,
            "gossip_s_per_round": scopes["dfl.gossip"] / rounds,
            "backward_s_per_round": summary["backward_s"] / rounds,
            "optimizer_s_per_round": scopes["dfl.local.update"] / rounds,
            "topk_threshold_s_per_round":
                scopes["compress.threshold"] / rounds}


def load(trace_dir: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """Device op events and the ``bench.*`` and ``dfl.*`` host spans of the
    newest trace under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        return {}, []
    pd = ProfileData.from_file(files[-1])
    device_ops: Dict[str, List[Event]] = {}
    host_spans: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("bench.", PROGRAM_SPAN)):
                        host_spans.append((e.name, e.start_ns, e.end_ns))
    return device_ops, host_spans


def tables(summary: Dict, busy_s: float, top: int = 15) -> str:
    """The (phase, op) table and the idle table, as text."""
    lines = [f"device seconds by (phase, op), top {top} of busy "
             f"{busy_s:.3f} s:"]
    for key, v in sorted(summary.get("scope_ops", {}).items(),
                         key=lambda kv: -kv[1])[:top]:
        lines.append(f"  {v:10.4f} s {100 * v / (busy_s or 1):6.2f}%  {key}")
    lines.append("idle by program span:")
    for key, v in sorted(summary.get("idle_by_program_span", {}).items(),
                         key=lambda kv: -kv[1]):
        lines.append(f"  {v * 1e3:10.3f} ms  {key}")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    import json
    import shutil

    from bench.run import (OUT_DIR, ROOT, NoChip, find_chips, setup_paths,
                           use_compile_cache)

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    setup_paths()
    use_compile_cache()
    import jax

    # JAX's cache key leaves metadata out by default: an executable that an
    # older program compiled without the scopes, in this checkout's cache,
    # would be loaded in place of this one and read nothing
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    from bench import trace as trace_lib
    from bench.program import Program, first_steps, run_window
    from bench.spec import Bench
    from bench.traffic import generator

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    try:
        find_chips(int(cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    config, traffic = bench.config(cell["config"]), bench.traffic(
        cell["traffic"])
    program = Program(config, traffic)
    pool = generator.make_pool(traffic, config["model"]["vocab_size"],
                               args.seed)
    state = program.build_state(args.seed)
    program.warm(state, program.batches(pool[0]))
    state, _ = first_steps(program, state, pool, 1, args.seed)
    out = {"workload": args.workload, "seed": args.seed,
           "setup_s": time.perf_counter() - T_START}
    tokens = program.tokens_per_superstep()
    state, wall, done, _ = run_window(program, state, pool, args.seconds,
                                      first=1)
    out["untraced"] = {"tokens_per_s": done * tokens / wall,
                       "supersteps": done, "wall_s": wall}

    trace_dir = os.path.join(OUT_DIR, f"scopes-{args.workload}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW):
        state, wall, done, _ = run_window(program, state, pool,
                                          args.seconds, first=1 + done)
        jax.block_until_ready(state)
    jax.profiler.stop_trace()
    out["traced"] = {"tokens_per_s": done * tokens / wall,
                     "supersteps": done, "wall_s": wall}

    hlo = program.compiled.as_text()
    paths = scope_paths(hlo)
    ops, spans = load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    kernels = trace_lib.kernel_names(hlo)
    base = trace_lib.reduce_events(
        ops, [s for s in spans if s[0].startswith("bench.")], kernels)
    summary = reduce_scopes(ops, spans, paths, kernels)
    rounds = done * int(traffic["superstep"])
    if summary.get("scopes") is None:
        print("bench: the compiled superstep carries none of the scopes "
              f"{SCOPES} (an executable compiled without them, loaded "
              "from the compile cache?)", file=sys.stderr)
    print(tables(summary, base.get("busy_s", 0.0)), file=sys.stderr)
    out.update(rounds=rounds, busy_s=base.get("busy_s"),
               window_s=base.get("window_s"),
               idle_by_span=base.get("idle_by_span"),
               per_round=per_round(summary, rounds),
               **{k: summary.get(k) for k in (
                   "scopes", "backward_s", "unscoped_s",
                   "idle_by_program_span")})
    out["scope_ops"] = dict(sorted(summary.get("scope_ops", {}).items(),
                                   key=lambda kv: -kv[1])[:40])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
