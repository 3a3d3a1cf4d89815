"""The comparison that decides ``correct``: the program's first superstep
against the plain reference's, from the same seeded weights and tokens.

Numbers compared (each a worst case, each against its limit in
``bench/limits/<cell>.json``):

  loss    the largest relative gap of a round's loss (mean over nodes and
          local steps) between program and reference;
  update  over nodes and leaves, the gap between the program's and the
          reference's norm of the parameters' change over the superstep,
          over the reference's norm of that leaf or of the median leaf,
          whichever is larger; leaves whose first reference gradient is
          under a thousandth of the median leaf's are left out (their
          change is round-off);
  spread  over leaves, the gap between the program's and the reference's
          largest distance of a node from the nodes' mean, over the same
          denominator as ``update`` (gossip that reaches the nodes or not);
  hat     (C-DFL) over nodes and leaves, the gap between the norms of the
          CHOCO shared estimates, over the reference's norm of that leaf
          or of the median leaf;
  drift   over nodes and moving leaves, the norm of the difference between
          the program's and the reference's parameters, over the
          reference's norm of the change, each on a fixed sample of
          ``SAMPLE`` evenly spaced elements of the leaf: where the gaps of
          norms above let rounding in opposite directions cancel, this
          sees each element's update;
  drift_l1  over nodes, the sum of |p - r| over the sum of |r - b|, the
          samples of every moving leaf taken together: a sound bfloat16
          program differs from the reference by one unit in the last
          place on a share of the elements, which this counts linearly
          where the squares of ``drift`` take its square root, so the
          lower precision of the control stands out further;
  hat_drift  (C-DFL) ``drift`` for the shared estimates, over the
          reference's norm of them.

A number that ``limits`` does not list is printed and not judged.
"""
from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

MOVING = 1e-3
SAMPLE = 65536


def sample_index(size: int) -> np.ndarray:
    """``SAMPLE`` evenly spaced element indices of a flattened leaf (all of
    them for a smaller leaf)."""
    m = min(size, SAMPLE)
    return ((np.arange(m, dtype=np.int64) * size) // m).astype(np.int32)


def _norm(v) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64)))))


def drift_leaves(prog, ref, base, keys) -> List[Dict[str, float]]:
    """Per node and leaf ||p - r|| / max(||r - b||, median leaf's), on
    the samples; ``base`` None measures against ||r||."""
    out = []
    for pn, rn in zip(prog, ref):
        den = {k: _norm(rn[k] - (base[k] if base is not None else 0.0))
               for k in keys}
        med = statistics.median(den.values())
        out.append({k: _rel(0.0, _norm(pn[k] - rn[k]), max(den[k], med))
                    for k in keys})
    return out


def _drift_l1(prog, ref, base, keys) -> float:
    """Over nodes, the worst sum |p - r| over sum |r - b|, every leaf's
    samples taken together."""
    worst = 0.0
    for pn, rn in zip(prog, ref):
        num = sum(_l1(pn[k] - rn[k]) for k in keys)
        den = sum(_l1(rn[k] - base[k]) for k in keys)
        worst = max(worst, _rel(0.0, num, den))
    return worst


def _l1(v) -> float:
    return float(np.sum(np.abs(np.asarray(v, np.float64))))


def _rel(p: float, r: float, den: float) -> float:
    return abs(p - r) / den if den > 0 else (0.0 if p == r else math.inf)


def _worst_leaf(prog: List[Dict[str, float]], ref: List[Dict[str, float]],
                keys) -> float:
    worst = 0.0
    for pn, rn in zip(prog, ref):
        med = statistics.median(rn[k] for k in keys)
        for k in keys:
            worst = max(worst, _rel(pn[k], rn[k], max(rn[k], med)))
    return worst


def moving_leaves(grad0: Dict[str, float]) -> List[str]:
    """Leaves whose first reference gradient is at least ``MOVING`` of
    the median leaf's."""
    gmed = statistics.median(grad0.values())
    return sorted(k for k in grad0 if grad0[k] >= MOVING * gmed)


def numbers(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    out = {"loss": max(_rel(p, r, abs(r))
                       for p, r in zip(prog["loss"], ref["loss"]))}
    if len(prog["loss"]) != len(ref["loss"]):
        out["loss"] = math.inf
    g = ref["grad0"]
    moving = moving_leaves(g)
    out["update"] = _worst_leaf(prog["change"], ref["change"], moving)
    change_med = statistics.median(
        v for node in ref["change"] for k, v in node.items() if k in moving)
    out["spread"] = max(
        _rel(prog["spread"][k], ref["spread"][k],
             max(max(node[k] for node in ref["change"]), change_med))
        for k in moving)
    per_leaf = drift_leaves(prog["sample"], ref["sample"], ref["sample0"],
                            moving)
    out["drift"] = max(max(node.values()) for node in per_leaf)
    out["drift_l1"] = _drift_l1(prog["sample"], ref["sample"],
                                ref["sample0"], moving)
    if "hat" in ref:
        out["hat"] = _worst_leaf(prog["hat"], ref["hat"], sorted(g))
        out["hat_drift"] = max(max(node.values()) for node in drift_leaves(
            prog["hat_sample"], ref["hat_sample"], None, sorted(g)))
    return {k: (v if math.isfinite(v) else float("inf"))
            for k, v in out.items()}


def judge(nums: Dict[str, float], limits: Optional[Dict[str, float]],
          finite: bool = True) -> Tuple[bool, Dict[str, Dict[str, Any]]]:
    """(correct, {name: {value, limit}}). Without limits nothing is
    correct; a non-finite loss anywhere is never correct."""
    table = {k: {"value": v, "limit": (limits or {}).get(k)}
             for k, v in nums.items()}
    ok = bool(limits) and finite and all(
        k in nums and nums[k] <= lim for k, lim in limits.items())
    return ok, table
