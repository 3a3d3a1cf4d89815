"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The dry-run entrypoint (``dryrun.py``) sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else (tests, benches, examples) sees the single real device.
"""
from __future__ import annotations

import jax

from repro.core.substrate import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants for the roofline (DESIGN.md section 6).
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link (intra-pod)
DCN_BW = 6.25e9                # bytes/s per chip (inter-pod, ~50 Gb/s)
HBM_PER_CHIP = 16 * 2**30      # v5e: 16 GiB
