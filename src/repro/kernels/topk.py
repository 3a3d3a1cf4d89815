"""TopK sparsification — two-pass Pallas TPU kernels.

The ``TopK`` compressor (paper Sec. V-A "Sparsification") keeps the
``k`` largest-magnitude coordinates of the flattened parameter vector and
zeros the rest. Its reference implementation sorts the WHOLE vector
(``jax.lax.top_k`` over d elements); the kernel path splits the work into
two tile passes so the O(d log d) select touches only a candidate subset:

  1. ``topk_partials_2d`` — per (BLOCK_ROWS x 128) tile, emit the tile's
     ``cand = min(k, tile)`` largest magnitudes. Every element of the
     GLOBAL top-k has per-tile rank <= k, so the union of per-tile
     partials is a superset of the global top-k and the k-th largest of
     the candidates is bit-identical to the k-th largest of the full
     vector — the select that follows (a plain ``lax.top_k`` over
     ``num_tiles * cand`` values, like the QSGD norm a single fused XLA
     reduction) therefore reproduces the reference threshold EXACTLY,
     ties included.
  2. ``topk_mask_2d`` — element-wise keep-or-zero against the threshold
     scalar, ``out = where(|x| >= thresh, x, 0)``, one VMEM pass.

Magnitudes are compared in the INPUT dtype (no f32 upcast): the reference
``jax.lax.top_k(jnp.abs(flat), k)`` sorts bf16 magnitudes as bf16, and
matching its tie behaviour bitwise requires comparing the same values.
Zero padding from the tile grid is harmless: pad magnitudes are 0, so a
pad can enter the candidate set only when the true threshold is already
0 — in which case the threshold is 0 either way.

Pass 1 calls ``lax.top_k`` inside the kernel body, which the Mosaic TPU
compiler does not lower — the registry marks it ``mosaic=False`` and on
TPU the dispatcher replaces pass 1 and its select with a sort-free
plain-XLA select (pass 2 stays a kernel there):
``repro.kernels.ops._kth_largest_magnitude`` bisects over the magnitude's
bit pattern, one compare-and-count reduction over the vector per bit
(15 for bf16, 31 for f32). With the sign bit cleared, a float's bits order
as an unsigned integer exactly as the magnitudes do, so the largest
pattern ``t`` with ``count(bits >= t) >= k`` is bit for bit the k-th
largest magnitude, ties included. Off-TPU both passes run in interpret
mode; all three modes are validated bitwise against the
``repro.core.compression.TopK`` oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256
LANES = 128


def _partials_kernel(x_ref, out_ref, *, cand: int):
    mag = jnp.abs(x_ref[...]).reshape(-1)
    out_ref[...] = jax.lax.top_k(mag, cand)[0].reshape(1, cand)


def topk_partials_2d(x2d: jnp.ndarray, *, cand: int,
                     interpret: bool = False) -> jnp.ndarray:
    """Per-tile top-``cand`` magnitudes: (rows, 128) -> (num_tiles, cand).

    ``cand`` must be ``min(k, BLOCK_ROWS * LANES)`` for the candidate-set
    superset property (module docstring) to hold.
    """
    rows, lanes = x2d.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0, x2d.shape
    assert 1 <= cand <= BLOCK_ROWS * LANES, cand
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        functools.partial(_partials_kernel, cand=cand),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, cand), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows // BLOCK_ROWS, cand),
                                       x2d.dtype),
        interpret=interpret,
    )(x2d)


def _mask_kernel(thresh_ref, x_ref, out_ref):
    x = x_ref[...]
    # |x| widened to f32 is exact and keeps its order, so this is the
    # leaf-dtype comparison; Mosaic extracts only 32-bit scalars.
    keep = jnp.abs(x).astype(jnp.float32) >= thresh_ref[0, 0]
    out_ref[...] = jnp.where(keep, x, jnp.zeros_like(x))


def topk_mask_2d(x2d: jnp.ndarray, thresh: jnp.ndarray, *,
                 interpret: bool = False) -> jnp.ndarray:
    """Keep-or-zero against the threshold, in the input dtype.

    ``thresh``: (1, 1) scalar tile — the k-th largest |x| from the select
    pass (in x's dtype; it enters the kernel widened to f32). Keeps ``|x| >= thresh`` (ties INCLUSIVE, matching the reference
    compressor — a few extra tied coordinates still satisfy Assumption 2).
    """
    rows, lanes = x2d.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0, x2d.shape
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        _mask_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
    )(thresh.astype(jnp.float32), x2d)
