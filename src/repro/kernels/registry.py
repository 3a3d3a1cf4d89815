"""Kernel registry: lazy backend detection, per-op dispatch guards, parity.

This module is the single place that decides HOW a compression/gossip
kernel runs:

  * ``backend()`` — the JAX default backend, detected LAZILY on first use
    and cached (``reset_backend_cache()`` un-caches, for tests and for
    programs that initialize jax backends after import). The old
    ``ops.ON_TPU`` module constant was computed at import time, so
    importing ``repro.kernels`` before backend selection silently pinned
    every kernel to interpret mode forever — the failure mode this
    module exists to remove.
  * ``KernelOp`` / ``get_op`` / ``list_ops`` — one registry entry per
    public kernel with its Mosaic-compilability flag, parity oracle, and
    bitwise contract.
  * ``resolve_mode(name, interpret)`` — the per-op dispatch rule:

        explicit interpret=True               -> "interpret"  (Python-
                                                 eval the kernel)
        interpret=False or on TPU, op.mosaic  -> "mosaic"     (TPU
                                                 compile)
        interpret=False or on TPU, not mosaic -> "fallback"   (plain-XLA
                                                 reference path; e.g. the
                                                 TopK candidate pass calls
                                                 lax.top_k in-kernel,
                                                 which Mosaic does not
                                                 lower)
        None, off TPU                         -> "interpret"

  * ``parity_suite()`` — the reference-parity harness: every registered
    op is run (in interpret mode, or compiled with ``interpret=False``)
    against its ``repro.kernels.ref`` oracle over a shape/dtype sweep;
    ops with ``bitwise=True`` must match EXACTLY.
    ``tests/test_kernels.py``, ``benchmarks/bench_kernels`` and
    ``chip_smoke.py`` (compiled, on the chip) consume this, so a new
    kernel cannot land without a mechanically-checked oracle.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "backend",
    "reset_backend_cache",
    "on_tpu",
    "KernelOp",
    "get_op",
    "list_ops",
    "resolve_mode",
    "resolve_interpret",
    "parity_suite",
    "PARITY_SHAPES",
    "PARITY_DTYPES",
]

_BACKEND_CACHE: Optional[str] = None


def backend() -> str:
    """The jax default backend ("cpu"/"gpu"/"tpu"), cached on FIRST CALL —
    never at import time, so backend selection that happens after
    ``import repro.kernels`` (distributed init, ``jax.config`` updates,
    test harnesses) is still honored by kernel dispatch."""
    global _BACKEND_CACHE
    if _BACKEND_CACHE is None:
        _BACKEND_CACHE = jax.default_backend()
    return _BACKEND_CACHE


def reset_backend_cache() -> None:
    """Forget the cached backend (next ``backend()`` call re-detects)."""
    global _BACKEND_CACHE
    _BACKEND_CACHE = None


def on_tpu() -> bool:
    return backend() == "tpu"


def _max_err(got, want) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32).reshape(-1)
                                 - jnp.asarray(want,
                                               jnp.float32).reshape(-1))))


@dataclasses.dataclass(frozen=True)
class KernelOp:
    """One registered kernel op.

    mosaic:  the kernel body lowers under the Mosaic TPU compiler (ops
             with ``mosaic=False`` dispatch to a plain-XLA fallback on
             TPU instead of crashing the compile).
    bitwise: the interpret-mode kernel must match its oracle EXACTLY
             (parity_suite enforces err == 0.0).
    parity:  (key, shape, dtype, interpret) -> max |kernel - oracle| in
             f32, running the kernel (interpret mode unless
             ``interpret=False``) against the ref oracle.
    """

    name: str
    mosaic: bool
    bitwise: bool
    doc: str
    parity: Callable[[jax.Array, Tuple[int, ...], Any, bool], float]


def _parity_qsgd(key, shape, dtype, interpret) -> float:
    from repro.kernels import ops, ref

    k1, k2 = jax.random.split(key)
    x = (jax.random.normal(k1, shape, jnp.float32) * 3).astype(dtype)
    noise = jax.random.uniform(k2, shape)
    d = int(np.prod(shape))
    s = 16.0
    c = 1.0 + min(d / (s * s), d ** 0.5 / s)
    got = ops.qsgd_quantize(x, noise, levels=16, interpret=interpret)
    want = ref.qsgd_ref(x, noise, levels=16, c=c)
    return _max_err(got, want)


def _parity_gossip_mix(key, shape, dtype, interpret) -> float:
    from repro.kernels import ops, ref

    deg = 2
    x = jax.random.normal(jax.random.fold_in(key, 0), shape).astype(dtype)
    nbrs = jax.random.normal(jax.random.fold_in(key, 1),
                             (deg,) + tuple(shape)).astype(dtype)
    w = jnp.concatenate([jnp.asarray([0.5]), jnp.full((deg,), 0.25)])
    got = ops.gossip_mix(x, nbrs, w, interpret=interpret)
    want = ref.gossip_mix_ref(x, nbrs, w)
    return _max_err(got, want)


def _parity_choco_move(key, shape, dtype, interpret) -> float:
    from repro.kernels import ops, ref

    x, y, my = (jax.random.normal(jax.random.fold_in(key, i),
                                  shape).astype(dtype) for i in range(3))
    got = ops.choco_move(x, y, my, 0.37, interpret=interpret)
    want = ref.choco_move_ref(x, y, my, 0.37)
    return max(_max_err(got[0], want[0]), _max_err(got[1], want[1]))


def _parity_topk(key, shape, dtype, interpret) -> float:
    from repro.kernels import ops, ref

    x = jax.random.normal(key, shape).astype(dtype)
    k = max(1, int(np.prod(shape)) // 4)
    got = ops.top_k_compress(x, k, interpret=interpret)
    want = ref.top_k_ref(x, k)
    return _max_err(got, want)


def _parity_topk_mask(key, shape, dtype, interpret) -> float:
    # the mask kernel ALONE against a hand-built threshold (what the TPU
    # fallback mode keeps as a compiled kernel), independent of the
    # candidate-select pass.
    from repro.kernels import ops, topk as topk_mod

    x = jax.random.normal(key, shape).astype(dtype)
    flat = x.reshape(-1)
    thresh = jnp.sort(jnp.abs(flat))[flat.size // 2]
    x2d, n = ops._to_2d(x)
    out2d = topk_mod.topk_mask_2d(x2d, thresh.reshape(1, 1),
                                  interpret=interpret)
    got = ops._from_2d(out2d, n, x.shape, x.dtype)
    want = jnp.where(jnp.abs(flat) >= thresh, flat,
                     0.0).reshape(x.shape).astype(x.dtype)
    return _max_err(got, want)


def _parity_choco_qsgd(key, shape, dtype, interpret) -> float:
    from repro.kernels import ops, ref

    ks = [jax.random.fold_in(key, i) for i in range(4)]
    x, y, my = (jax.random.normal(k, shape).astype(dtype) for k in ks[:3])
    noise = jax.random.uniform(ks[3], shape)
    d = int(np.prod(shape))
    s = 16.0
    c = 1.0 + min(d / (s * s), d ** 0.5 / s)
    got = ops.choco_qsgd_move(x, y, my, 0.5, noise, levels=16,
                              interpret=interpret)
    want = ref.choco_qsgd_ref(x, y, my, 0.5, noise, levels=16, c=c)
    return max(_max_err(got[0], want[0]), _max_err(got[1], want[1]))


def _parity_choco_topk(key, shape, dtype, interpret) -> float:
    from repro.kernels import ops, ref

    x, y, my = (jax.random.normal(jax.random.fold_in(key, i),
                                  shape).astype(dtype) for i in range(3))
    k = max(1, int(np.prod(shape)) // 4)
    got = ops.choco_topk_move(x, y, my, 0.5, k, interpret=interpret)
    want = ref.choco_topk_ref(x, y, my, 0.5, k)
    return max(_max_err(got[0], want[0]), _max_err(got[1], want[1]))


_REGISTRY: Dict[str, KernelOp] = {}


def _register(name: str, **kw) -> None:
    _REGISTRY[name] = KernelOp(name=name, **kw)


_register("qsgd_quantize", mosaic=True, bitwise=False,
          doc="QSGD stochastic quantization (element-wise, norm fed in)",
          parity=_parity_qsgd)
_register("gossip_mix", mosaic=True, bitwise=False,
          doc="fused weighted gossip accumulate over deg neighbor copies",
          parity=_parity_gossip_mix)
_register("choco_move", mosaic=True, bitwise=False,
          doc="CHOCO consensus move, (x_new, diff) in one pass",
          parity=_parity_choco_move)
_register("topk_partials", mosaic=False, bitwise=True,
          doc="per-tile top-cand magnitude candidates (lax.top_k "
              "in-kernel: interpret only; the fallback selects the "
              "threshold by bisection over the magnitude bits, no sort)",
          parity=_parity_topk)
_register("topk_mask", mosaic=True, bitwise=True,
          doc="keep-or-zero against the TopK threshold scalar",
          parity=_parity_topk_mask)
_register("choco_qsgd", mosaic=True, bitwise=False,
          doc="fused CHOCO move + QSGD compress + estimate update",
          parity=_parity_choco_qsgd)
_register("choco_topk", mosaic=True, bitwise=False,
          doc="fused CHOCO move + TopK compress + estimate update",
          parity=_parity_choco_topk)


def get_op(name: str) -> KernelOp:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel op {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def list_ops() -> List[KernelOp]:
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def resolve_mode(name: str, interpret: Optional[bool] = None) -> str:
    """Per-op dispatch decision: "interpret" | "mosaic" | "fallback".

    ``interpret=None`` (the default everywhere) resolves from the LAZILY
    detected backend and the op's Mosaic flag; an explicit bool always
    wins over the backend (tests force interpret=True; ``interpret=False``
    compiles for the TPU wherever this runs, which is how the kernels are
    compiled for a described chip). An op Mosaic cannot lower takes its
    plain-XLA fallback whenever it is not interpreted.
    """
    op = get_op(name)
    if interpret is True:
        return "interpret"
    if interpret is False or on_tpu():
        return "mosaic" if op.mosaic else "fallback"
    return "interpret"


def resolve_interpret(name: str, interpret: Optional[bool] = None) -> bool:
    """``resolve_mode`` narrowed to the ops that never fall back."""
    mode = resolve_mode(name, interpret)
    assert mode != "fallback", (
        f"op {name!r} resolved to the XLA fallback; call its fallback-aware"
        " dispatcher instead of forcing a pallas_call")
    return mode == "interpret"


PARITY_SHAPES: Tuple[Tuple[int, ...], ...] = (
    (64,), (1000,), (256, 128), (3, 5, 7), (32768,), (300, 70), (32769,))
PARITY_DTYPES = (jnp.float32, jnp.bfloat16)


def parity_suite(
    shapes: Sequence[Tuple[int, ...]] = PARITY_SHAPES,
    dtypes: Sequence[Any] = PARITY_DTYPES,
    seed: int = 0,
    ops: Optional[Sequence[str]] = None,
    interpret: bool = True,
) -> List[Dict[str, Any]]:
    """Run every registered op's kernel against its oracle: in interpret
    mode, or compiled (``interpret=False``, on the chip).

    Returns one record per (op, shape, dtype):
    ``{"op", "shape", "dtype", "max_err", "bitwise", "ok"}`` where ``ok``
    requires ``max_err == 0.0`` for bitwise ops and ``max_err < tol``
    (1e-5 f32 / 1e-2 bf16 — the one-ulp bf16 rounding the unfused
    kernels already exhibit) otherwise.
    """
    import zlib

    records: List[Dict[str, Any]] = []
    names = [o.name for o in list_ops()] if ops is None else list(ops)
    for name in names:
        op = get_op(name)
        for shape in shapes:
            for dtype in dtypes:
                # deterministic across processes (str hash() is salted)
                case = f"{name}:{tuple(shape)}".encode()
                key = jax.random.key(
                    (seed * 7919 + zlib.crc32(case)) % 2 ** 31)
                err = op.parity(key, tuple(shape), dtype, interpret)
                tol = 0.0 if op.bitwise else (
                    1e-5 if dtype == jnp.float32 else 1e-2)
                records.append({
                    "op": name,
                    "shape": list(shape),
                    "dtype": np.dtype(dtype).name,
                    "max_err": err,
                    "bitwise": op.bitwise,
                    "ok": bool(err <= tol),
                })
    return records
