"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the device's busy time (the union of its operations' intervals), the
device time of each named operation and kernel, and the idle gaps, each
labelled by the harness span (``bench.*``) the host was in during it.

The window is the host span ``bench.window``; device events are clipped
to it. A device op event is named by its HLO instruction's text; loops
and calls are counted in the busy time but not as ops, since their
intervals hold the ops they run. Kernel names come from the compiled
program (``kernel_names``): each Mosaic custom call's instruction name
mapped to the name of its kernel function.
"""
from __future__ import annotations

import base64
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
HARNESS_SPAN = "bench."
PROGRAM_SPAN = "dfl."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def kernel_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> kernel function name, for every Mosaic custom
    call of an optimized HLO module."""
    out = {}
    for m in re.finditer(
            r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
            r'[^\n]*?"body":"([A-Za-z0-9+/=]*)"', hlo_text):
        try:
            body = base64.b64decode(m.group(2))
        except ValueError:
            continue
        names = [n.decode() for n in
                 re.findall(rb"[A-Za-z_][A-Za-z0-9_]*kernel\b", body)]
        if names:
            out[m.group(1)] = names[0]
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    out, cur = [], window[0]
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


CONTAINERS = ("while", "conditional", "call")


def instruction(event: str) -> str:
    """The HLO instruction name of a device op event, whose name is the
    instruction's text (``%fusion.12 = bf16[...] fusion(...), ...``) or the
    bare name."""
    m = re.match(r"%?([^\s=]+)", event)
    return m.group(1) if m else event


def op_label(event: str, kernels: Dict[str, str]) -> Optional[str]:
    """A stable label for a device op: its kernel's name for a Mosaic
    call, else the instruction name without its number, with the fusion
    kind for fusions (``fusion:kOutput``). None for a loop or call, whose
    interval holds other ops."""
    name = instruction(event)
    if name in kernels:
        return kernels[name]
    root = re.sub(r"\.\d+$", "", name)
    if root.split("_")[0] in CONTAINERS or root in CONTAINERS:
        return None
    kind = re.search(r"kind=(k\w+)", event)
    if "fusion" in root and kind:
        root = f"{root}:{kind.group(1)}"
    return root


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[str, float, float]],
                  kernels: Optional[Dict[str, str]] = None) -> Dict:
    """The reduction itself, on plain lists (times in ns).

    ``device_ops``: per device, (op name, start, end); ``host_spans``:
    (span name, start, end) of host spans, one of them ``bench.window``;
    idle gaps are labelled by the harness's (``bench.*``) alone."""
    kernels = kernels or {}
    win = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not win or not device_ops:
        return {}
    window = (min(s for s, _ in win), max(e for _, e in win))
    span = window[1] - window[0]
    per_op: Dict[str, float] = {}
    busy_total = 0.0
    gap_by: Dict[str, float] = {}
    spans = [(n, s, e) for n, s, e in host_spans
             if n.startswith(HARNESS_SPAN) and n != WINDOW]
    for ops in device_ops.values():
        clipped = []
        for name, s, e in ops:
            s, e = max(s, window[0]), min(e, window[1])
            if e <= s:
                continue
            clipped.append((s, e))
            label = op_label(name, kernels)
            if label is not None:
                per_op[label] = per_op.get(label, 0.0) + (e - s)
        busy = union(clipped)
        busy_total += sum(e - s for s, e in busy)
        for gs, ge in gaps(busy, window):
            best, best_ov = "host.other", 0.0
            for n, s, e in spans:
                ov = min(e, ge) - max(s, gs)
                if ov > best_ov:
                    best, best_ov = n, ov
            gap_by[best] = gap_by.get(best, 0.0) + (ge - gs)
    ndev = len(device_ops)
    ns = 1e-9
    return {
        "window_s": span * ns,
        "busy_s": busy_total / ndev * ns,
        "devices": ndev,
        "ops": {k: v / ndev * ns for k, v in per_op.items()},
        "idle_by_span": {k: v / ndev * ns for k, v in gap_by.items()},
    }


def load(trace_dir: str) -> Tuple[Dict[str, List[Tuple[str, float, float]]],
                                  List[Tuple[str, float, float]]]:
    """Device op events, and the harness's (``bench.*``) and the program's
    (``dfl.*``) host spans, of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        return {}, []
    pd = ProfileData.from_file(files[-1])
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    host_spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((HARNESS_SPAN, PROGRAM_SPAN)):
                        host_spans.append((e.name, e.start_ns, e.end_ns))
    return device_ops, host_spans


def breakdown(summary: Dict) -> Dict[str, List]:
    ops = sorted(summary.get("ops", {}).items(), key=lambda kv: -kv[1])
    idle = sorted(summary.get("idle_by_span", {}).items(),
                  key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:10]],
            "idle_gaps": [[k, v] for k, v in idle[:10]]}
