"""Fused CHOCO-G compress-and-move — Pallas TPU kernels.

One C-DFL inner communication step (Alg. 2 lines 6-11) per node is, after
the neighbor estimates have been mixed (``mixed_y = sum_j c_ji y_j``):

    x_new = x + gamma * (mixed_y - y)        # consensus move   (l.6)
    q     = Q(x_new - y)                     # compress the gap (l.7)
    y_new = y + q                            # estimate update  (l.11)

The unfused kernel path runs this as THREE separate padded round-trips
over the flattened parameter buffer (``choco_update`` kernel -> ``qsgd``
or ``topk`` kernel -> an XLA add), materializing the intermediate ``diff``
and ``q`` tensors in HBM. These kernels emit ``(x_new, y_new)`` directly
in a single VMEM pass over ``(x, y, mixed_y)``:

  * ``choco_qsgd_2d`` — Q = QSGD random quantization. The global vector
    norm (a reduction) is computed outside and arrives with gamma as a
    (1, 2) f32 scalar tile; the per-leaf uniform noise rides in as a
    tensor so the kernel stays deterministic against the oracle.
  * ``choco_topk_2d`` — Q = TopK sparsification. The threshold (the k-th
    largest |x_new - y|, a global select — see ``repro.kernels.topk``)
    arrives as a (1, 1) scalar tile in the LEAF dtype.

Bit-compat contract with the unfused kernels: ``x_new`` is computed in
f32 and cast once to the leaf dtype; the compressed gap is quantized on
``(x_new - y)`` CAST TO THE LEAF DTYPE first (the unfused path
materializes ``diff`` in the leaf dtype before compressing it), and
``y_new = y + q`` is accumulated in the leaf dtype (matching the unfused
XLA tree add). For f32 leaves the fused and unfused paths are bitwise
identical; bf16 agrees to the same one-ulp rounding the unfused kernels
already exhibit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256
LANES = 128


def _choco_qsgd_kernel(scal_ref, x_ref, y_ref, my_ref, noise_ref,
                       xout_ref, yout_ref, *, levels: float, c: float):
    gamma = scal_ref[0, 0]
    norm = scal_ref[0, 1]
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    my = my_ref[...].astype(jnp.float32)
    x_new = x + gamma * (my - y)
    xout_ref[...] = x_new.astype(xout_ref.dtype)
    # quantize the gap exactly as the unfused path sees it: materialized
    # in the leaf dtype, then upcast inside the quantizer.
    d = (x_new - y).astype(xout_ref.dtype).astype(jnp.float32)
    xi = noise_ref[...].astype(jnp.float32)
    safe = jnp.where(norm > 0.0, norm, 1.0)
    lvl = jnp.floor(levels * jnp.abs(d) / safe + xi)
    q = jnp.sign(d) * safe * lvl / (levels * c)
    q = jnp.where(norm > 0.0, q, 0.0).astype(yout_ref.dtype)
    yout_ref[...] = y_ref[...] + q


def choco_qsgd_2d(x2d: jnp.ndarray, y2d: jnp.ndarray, my2d: jnp.ndarray,
                  noise2d: jnp.ndarray, scal: jnp.ndarray, *, levels: int,
                  c: float, interpret: bool = False):
    """Fused CHOCO step with QSGD compression: returns (x_new, y_new).

    All tensor operands (rows, 128) with rows % BLOCK_ROWS == 0;
    ``scal`` = [[gamma, norm]] as a (1, 2) f32 tile, where ``norm`` is
    ``||(x + gamma (my - y) - y).astype(dtype)||_2`` over the UNPADDED
    flat leaf (the same norm the unfused qsgd wrapper computes on the
    materialized diff).
    """
    rows, lanes = x2d.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0, x2d.shape
    assert scal.shape == (1, 2), scal.shape
    grid = (rows // BLOCK_ROWS,)
    blk = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_choco_qsgd_kernel, levels=float(levels),
                          c=float(c)),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0)), blk, blk, blk,
                  blk],
        out_specs=(blk, blk),
        out_shape=(jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
                   jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)),
        interpret=interpret,
    )(scal, x2d, y2d, my2d, noise2d)


def _choco_topk_kernel(gamma_ref, thresh_ref, x_ref, y_ref, my_ref, d_ref,
                       xout_ref, yout_ref):
    gamma = gamma_ref[0, 0]
    thresh = thresh_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    my = my_ref[...].astype(jnp.float32)
    x_new = x + gamma * (my - y)
    xout_ref[...] = x_new.astype(xout_ref.dtype)
    d = d_ref[...]
    # exact f32 widening of the leaf-dtype comparison (topk._mask_kernel)
    q = jnp.where(jnp.abs(d).astype(jnp.float32) >= thresh, d,
                  jnp.zeros_like(d))
    yout_ref[...] = y_ref[...] + q


def choco_topk_2d(x2d: jnp.ndarray, y2d: jnp.ndarray, my2d: jnp.ndarray,
                  d2d: jnp.ndarray, gamma: jnp.ndarray,
                  thresh: jnp.ndarray, *, interpret: bool = False):
    """Fused CHOCO step with TopK compression: returns (x_new, y_new).

    ``gamma``: (1, 1) f32 tile; ``d2d``: the gap
    diff = (x + gamma (my - y) - y) MATERIALIZED in the leaf dtype —
    the same tensor the threshold select reduced, fed back in rather
    than recomputed in-kernel so the ``|d| >= thresh`` mask decisions
    are exactly consistent with the threshold (a 1-ulp divergence
    between two compilations of the diff arithmetic could otherwise
    flip a boundary element in or out of the kept set); ``thresh``:
    (1, 1) tile in the LEAF dtype, the k-th largest |d| (magnitude
    comparison in the dtype the reference compressor sorts, see
    ``repro.kernels.topk``), widened to f32 on its way into the kernel.
    """
    rows, lanes = x2d.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0, x2d.shape
    grid = (rows // BLOCK_ROWS,)
    blk = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    scal = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        _choco_topk_kernel,
        grid=grid,
        in_specs=[scal, scal, blk, blk, blk, blk],
        out_specs=(blk, blk),
        out_shape=(jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
                   jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)),
        interpret=interpret,
    )(gamma, thresh.astype(jnp.float32), x2d, y2d, my2d, d2d)
