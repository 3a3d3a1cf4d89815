"""Attribute a traced window's device time to the program's named scopes,
and its idle gaps to the program's own host spans.

The program names its phases with ``jax.named_scope``, each name a dotted
``layer.part`` (``dfl.local``, ``dfl.local.update``, ``dfl.gossip``,
``compress.threshold``); XLA keeps the scope path in each instruction's
``op_name`` metadata, so the compiled module's text maps every device op
event (named by its instruction) to the scopes it runs under. Every such
name in the module is reported, so a scope a later program adds is read by
a metric reader alone. Backward is no scope of its own: it is the ops
under ``dfl.local`` whose ``op_name`` holds JAX's VJP marker
``transpose(`` (remat recomputation included). The executor opens
``jax.profiler.TraceAnnotation`` host spans named ``dfl.*`` (dispatch,
compile, prefetch build and take, metrics flush) on the profiler's clock.

``bench/run.py --trace 1`` merges ``reduce_scopes`` into the summary its
metric readers get, and prints ``tables`` on standard error.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace import (PROGRAM_SPAN, WINDOW, gaps, instruction, op_label,
                         union)

BACKWARD = "transpose("
NO_SPAN = "dfl.none"

Event = Tuple[str, float, float]

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)"
    r"|\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"^[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)+$")


def _marks(op_name: str) -> Tuple[str, ...]:
    """The named scopes on one ``op_name``, outermost first, and the VJP
    marker. A scope is a dotted name among the components of the path
    below its root (``jit(superstep)/...``); an ``op_name`` with no path
    is an argument's name (``state.params[...]``), not a scope."""
    found: List[str] = []
    for path in op_name.split(";"):             # ";" joins merged ops
        if "/" not in path:
            continue
        for part in re.split(r"[/()]", path.split("/", 1)[1]):
            if _SCOPE.match(part) and part not in found:
                found.append(part)
    return tuple(found) + ((BACKWARD,) if BACKWARD in op_name else ())


def scope_paths(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """Instruction name -> the named scopes, outermost first (and
    ``BACKWARD`` last), of an optimized HLO module. An instruction also
    takes the scopes of the instruction that calls its computation (a
    loop, call, conditional or fusion), recursively: a loop under
    ``dfl.local`` runs ``dfl.local`` work, and a layout ``copy`` XLA
    inserted there carries no metadata."""
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
            # the call graph of a module is a DAG
            marks = (inherited(up) if up is not None else ()) + own.get(
                name, ())
            memo[name] = tuple(dict.fromkeys(
                m for m in marks if m != BACKWARD)) + (
                (BACKWARD,) if BACKWARD in marks else ())
        return memo[name]

    return {name: inherited(name) for name in own}


def phase(marks: Sequence[str]) -> str:
    """A (scope, op) table's phase of one op: its innermost named scope,
    ``/backward`` under the VJP marker, or ``unscoped``."""
    scopes = [m for m in marks if m != BACKWARD]
    if not scopes:
        return "unscoped"
    return scopes[-1] + ("/backward" if BACKWARD in marks else "")


def _length(intervals) -> float:
    return sum(e - s for s, e in union(list(intervals)))


def reduce_scopes(device_ops: Dict[str, List[Event]],
                  host_spans: List[Event],
                  paths: Dict[str, Tuple[str, ...]],
                  kernels: Optional[Dict[str, str]] = None) -> Dict:
    """Device seconds per named scope of the module (``scopes``, keyed by
    name; nested scopes count in both), ``backward_s``, ``unscoped_s``
    (busy time in no scope), ``scope_ops`` (seconds per (phase, op
    label)) and ``idle_by_program_span``, over the ``bench.window`` span;
    averaged over devices as ``bench.trace.reduce_events`` averages. Empty
    without a window or device ops; ``scopes``, ``backward_s`` and
    ``unscoped_s`` are None when ``paths`` carries no scope (a module
    compiled without them). A loop's or call's interval counts for its
    scopes and in no (phase, op) pair."""
    kernels = kernels or {}
    win = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not win or not device_ops:
        return {}
    window = (min(s for s, _ in win), max(e for _, e in win))
    names = sorted({m for marks in paths.values() for m in marks
                    if m != BACKWARD})
    per_scope: Dict[str, float] = {s: 0.0 for s in names}
    pairs: Dict[str, float] = {}
    backward = unscoped = 0.0
    idle: Dict[str, float] = {}
    spans = [(n, s, e) for n, s, e in host_spans
             if n.startswith(PROGRAM_SPAN)]
    for ops in device_ops.values():
        every, scoped, bwd = [], [], []
        by_scope: Dict[str, list] = {s: [] for s in names}
        for name, s, e in ops:
            s, e = max(s, window[0]), min(e, window[1])
            if e <= s:
                continue
            every.append((s, e))
            marks = paths.get(instruction(name), ())
            for sc in marks:
                if sc != BACKWARD:
                    by_scope[sc].append((s, e))
            if any(m != BACKWARD for m in marks):
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
                   if names else None),
        "backward_s": backward * k if names else None,
        "unscoped_s": unscoped * k if names else None,
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


def per_round(summary: Dict, scope: str) -> Optional[float]:
    """Device seconds a round under ``scope``, with the window's
    ``rounds`` from the summary; None where the module carries no such
    scope or no round ran. What each ``bench/metrics`` reader of a phase
    returns."""
    scopes = summary.get("scopes") or {}
    rounds = summary.get("rounds")
    if scope not in scopes or not rounds:
        return None
    return scopes[scope] / rounds


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
