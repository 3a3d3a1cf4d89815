"""Jitted public wrappers around the Pallas kernels.

Handle arbitrary-shaped inputs: flatten, pad to the (BLOCK_ROWS x 128)
tile grid, run the kernel, unpad. Every wrapper takes ``interpret=None``
by default, resolved PER CALL through ``repro.kernels.registry`` — the
backend is detected lazily on first use (never at import time), and each
op carries its own interpret/Mosaic/XLA-fallback guard
(``registry.resolve_mode``). Off-TPU the kernels run in interpret mode
(the kernel body evaluated in Python, validated against the
``repro.kernels.ref`` oracles); on TPU the same call sites compile to
Mosaic, except ops the registry marks ``mosaic=False`` which dispatch to
an equivalent plain-XLA path.

Entry points
  * ``qsgd_quantize``   — QSGD random quantization (norm fed as scalar).
  * ``gossip_mix``      — fused weighted neighbor accumulate.
  * ``choco_move``      — CHOCO consensus move, (x_new, diff) one pass.
  * ``topk_threshold``  — k-th largest |x|: the two-pass candidate
                          select (``repro.kernels.topk``), or on TPU the
                          bisection select below.
  * ``top_k_compress``  — kernel-backed TopK sparsifier; bitwise-matches
                          ``repro.core.compression.TopK``.
  * ``choco_qsgd_move`` / ``choco_topk_move`` — the FUSED CHOCO
    compress-and-move step, (x, y, mixed_y) -> (x_new, y_new) in a
    single kernel pass (``repro.kernels.choco_fused``) instead of the
    three separate padded round-trips the unfused composition pays.

``op_stats()`` exposes pad-roundtrip / pallas-call / threshold-bisection
counters so benchmarks and tests can ASSERT the fused paths touch the
buffer fewer times and which select a path traced; they
tick when wrapper bodies execute, so count over ``eager_impl`` calls
(un-jitted, deterministic per call) — see ``benchmarks/bench_kernels``.
Scope a measurement with ``with op_stats_delta() as d:`` — snapshot
arithmetic, no global reset, so concurrent/nested measurement scopes
can't clobber each other.

The TopK threshold select runs under ``jax.named_scope(
"compress.threshold")``, so a profiler trace attributes its device time
by the HLO ``op_name`` metadata. Where the candidate kernel cannot run
(Mosaic does not lower its in-kernel ``lax.top_k``), the select is
``_kth_largest_magnitude``: a bisection over the magnitude's bit pattern,
one compare-and-count reduction per bit and no sort. With the sign bit
cleared, a float's bits order as an unsigned integer exactly as its
magnitudes do (``-0.0`` and ``+0.0`` both read 0, ``inf`` is the largest
non-NaN pattern), so the largest pattern ``t`` with ``count(|x| >= t) >=
k`` is bit for bit the value ``lax.top_k(jnp.abs(x), k)[0][k - 1]``
returns, ties included.
"""
from __future__ import annotations

import contextlib
import warnings
from typing import Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import choco_fused as _fused
from repro.kernels import choco_update as _choco
from repro.kernels import gossip_mix as _mix
from repro.kernels import qsgd as _qsgd
from repro.kernels import registry
from repro.kernels import topk as _topk

_TILE = _qsgd.BLOCK_ROWS * _qsgd.LANES
# _to_2d pads every buffer to THIS tile grid for every kernel, and the
# TopK candidate bound (cand = min(k, _TILE)) leans on it for the
# bitwise-superset property — so all kernel modules must agree on it.
assert all(m.BLOCK_ROWS * m.LANES == _TILE
           for m in (_choco, _fused, _mix, _topk)), (
    "kernel modules disagree on the (BLOCK_ROWS x LANES) tile size")

_STATS: Dict[str, int] = {"pad_roundtrips": 0, "pallas_calls": 0,
                          "threshold_bisections": 0}


def op_stats() -> Dict[str, int]:
    """Counters of buffer work: ``pad_roundtrips`` (flatten/pad/unpad
    cycles through ``_to_2d``), ``pallas_calls`` (kernel launches) and
    ``threshold_bisections`` (TopK thresholds selected by bisection).
    Python-side: they tick when a wrapper body EXECUTES — once per jit
    trace through the public entry points, or once per call through
    ``eager_impl`` (how ``benchmarks/bench_kernels`` counts
    fused-vs-unfused buffer passes deterministically)."""
    return dict(_STATS)


class OpStatsDelta:
    """Counter deltas observed inside an ``op_stats_delta()`` block.

    Values are populated at context EXIT; reading earlier raises (there
    is no meaningful partial answer while the block is still counting).
    """

    def __init__(self):
        self._delta: Optional[Dict[str, int]] = None

    def _close(self, delta: Dict[str, int]) -> None:
        self._delta = delta

    def as_dict(self) -> Dict[str, int]:
        if self._delta is None:
            raise RuntimeError(
                "op_stats_delta block still open — deltas exist only "
                "after the with-block exits")
        return dict(self._delta)

    def __getitem__(self, key: str) -> int:
        return self.as_dict()[key]

    @property
    def pad_roundtrips(self) -> int:
        return self["pad_roundtrips"]

    @property
    def pallas_calls(self) -> int:
        return self["pallas_calls"]

    @property
    def threshold_bisections(self) -> int:
        return self["threshold_bisections"]


@contextlib.contextmanager
def op_stats_delta() -> Iterator[OpStatsDelta]:
    """Scoped counter attribution: yields an ``OpStatsDelta`` whose
    per-key deltas (work done INSIDE the block) are readable after exit.

    Pure snapshot arithmetic against the module counters — nothing is
    reset, so nested scopes and interleaved measurement sites (the
    benchmark suite, per-superstep telemetry in ``launch.train``) each
    see exactly their own window::

        with op_stats_delta() as d:
            ops.eager_impl("choco_move")(x, y, my, 0.5, interpret=True)
        assert d.pad_roundtrips == 3
    """
    before = dict(_STATS)
    d = OpStatsDelta()
    try:
        yield d
    finally:
        d._close({k: _STATS[k] - before.get(k, 0) for k in _STATS})


def __getattr__(name: str):
    if name == "ON_TPU":
        warnings.warn(
            "repro.kernels.ops.ON_TPU is deprecated: it was computed at "
            "import time and went stale when backends initialized later. "
            "Backend detection is lazy now — use "
            "repro.kernels.registry.on_tpu() / resolve_mode().",
            DeprecationWarning, stacklevel=2)
        return registry.on_tpu()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _to_2d(x: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    _STATS["pad_roundtrips"] += 1
    flat = x.reshape(-1)
    n = flat.size
    pad = (-n) % _TILE
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _qsgd.LANES), n


def _from_2d(x2d: jnp.ndarray, n: int, shape, dtype) -> jnp.ndarray:
    return x2d.reshape(-1)[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# QSGD / gossip / CHOCO move (PR-1 kernels, now lazily dispatched)
# ---------------------------------------------------------------------------


def _qsgd_quantize_impl(x, noise, *, levels: int, interpret: bool):
    d = x.size
    s = float(levels)
    c = 1.0 + min(d / (s * s), (d ** 0.5) / s)
    x2d, n = _to_2d(x)
    n2d, _ = _to_2d(noise)
    norm = jnp.linalg.norm(x.reshape(-1).astype(jnp.float32)).reshape(1, 1)
    _STATS["pallas_calls"] += 1
    out = _qsgd.qsgd_quantize_2d(x2d, n2d, norm, levels=levels, c=c,
                                 interpret=interpret)
    return _from_2d(out, n, x.shape, x.dtype)


_qsgd_quantize = jax.jit(_qsgd_quantize_impl,
                         static_argnames=("levels", "interpret"))


def qsgd_quantize(x: jnp.ndarray, noise: jnp.ndarray, *, levels: int = 16,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """QSGD with delta = 1/c, c = 1 + min(d/s^2, sqrt(d)/s); same output
    as ``repro.core.compression.QSGD`` given the same uniform ``noise``."""
    interpret = registry.resolve_interpret("qsgd_quantize", interpret)
    return _qsgd_quantize(x, noise, levels=levels, interpret=interpret)


def _gossip_mix_impl(x, neighbors, weights, *, interpret: bool):
    deg = neighbors.shape[0]
    x2d, n = _to_2d(x)
    nbr2d = jax.vmap(lambda t: _to_2d(t)[0])(neighbors.reshape(deg, -1))
    w = weights.reshape(1, deg + 1).astype(jnp.float32)
    _STATS["pallas_calls"] += 1
    out = _mix.gossip_mix_2d(x2d, nbr2d, w, interpret=interpret)
    return _from_2d(out, n, x.shape, x.dtype)


_gossip_mix = jax.jit(_gossip_mix_impl, static_argnames=("interpret",))


def gossip_mix(x: jnp.ndarray, neighbors: jnp.ndarray,
               weights: jnp.ndarray, *,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """out = weights[0]*x + sum_j weights[1+j]*neighbors[j], one pass."""
    interpret = registry.resolve_interpret("gossip_mix", interpret)
    return _gossip_mix(x, neighbors, weights, interpret=interpret)


def _choco_move_impl(x, y, mixed_y, gamma, *, interpret: bool):
    x2d, n = _to_2d(x)
    y2d, _ = _to_2d(y)
    my2d, _ = _to_2d(mixed_y)
    g = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    _STATS["pallas_calls"] += 1
    xo, do = _choco.choco_move_2d(x2d, y2d, my2d, g, interpret=interpret)
    return (_from_2d(xo, n, x.shape, x.dtype),
            _from_2d(do, n, x.shape, x.dtype))


_choco_move = jax.jit(_choco_move_impl, static_argnames=("interpret",))


def choco_move(x: jnp.ndarray, y: jnp.ndarray, mixed_y: jnp.ndarray,
               gamma, *, interpret: Optional[bool] = None):
    """Fused CHOCO consensus step: returns (x_new, d = x_new - y)."""
    interpret = registry.resolve_interpret("choco_move", interpret)
    return _choco_move(x, y, mixed_y, gamma, interpret=interpret)


# ---------------------------------------------------------------------------
# TopK (two-pass: per-tile candidates -> global select -> mask)
# ---------------------------------------------------------------------------


_UNSIGNED = {2: jnp.uint16, 4: jnp.uint32}


def _kth_largest_magnitude(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """The k-th largest |x| in x's dtype, bit for bit what
    ``lax.top_k(jnp.abs(x.reshape(-1)), k)[0][k - 1]`` returns, without
    a sort.

    Bisection over the w-bit pattern of the magnitude (module
    docstring): every pattern with the sign cleared lies in [0, 2^(w-1)),
    so w - 1 halvings of that range pin the largest ``t`` with
    ``count(bits >= t) >= k``. Each halving is one reduction that clears
    the sign bit, compares and counts in place, so no magnitude array is
    kept; ``x`` keeps its shape, since flattening a tiled TPU array is a
    relayout copy. Counts are int32, hence the bound on the size."""
    if x.size >= 2**31:
        raise ValueError(
            f"TopK bisection counts in int32; a size-{x.size} vector "
            "has 2^31 elements or more")
    try:
        uint = _UNSIGNED[x.dtype.itemsize]
    except KeyError:
        raise ValueError(
            f"no bisection select for dtype {x.dtype}") from None
    width = 8 * x.dtype.itemsize
    sign_clear = uint((1 << (width - 1)) - 1)
    _STATS["threshold_bisections"] += 1

    def halve(_, lohi):
        # Tied to the loop-variant bounds, x stays inside the body:
        # otherwise XLA hoists its bitcast out of the loop as an array.
        xv, (lo, hi) = jax.lax.optimization_barrier((x, lohi))
        mid = lo + (hi - lo) // 2
        bits = jax.lax.bitcast_convert_type(xv, uint) & sign_clear
        ok = jnp.sum(bits >= mid, dtype=jnp.int32) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, width - 1, halve,
                              (uint(0), uint(1 << (width - 1))))
    return jax.lax.bitcast_convert_type(lo, x.dtype)


def _threshold_impl(x: jnp.ndarray, k: int, mode: str) -> jnp.ndarray:
    """k-th largest |x| as a scalar in x's dtype. ``mode`` per
    ``registry.resolve_mode("topk_partials", ...)``: the candidate pass
    runs as a kernel ("interpret"/"mosaic") or the bisection select reads
    the whole vector ("fallback"); all three produce the SAME threshold
    bit-for-bit (see repro.kernels.topk)."""
    flat = x.reshape(-1)
    if not 1 <= k <= flat.size:
        raise ValueError(
            f"TopK k={k} out of range for a size-{flat.size} vector")
    with jax.named_scope("compress.threshold"):
        if mode == "fallback":
            return _kth_largest_magnitude(x, k)
        x2d, _ = _to_2d(x)
        cand = min(k, _TILE)
        _STATS["pallas_calls"] += 1
        parts = _topk.topk_partials_2d(x2d, cand=cand,
                                       interpret=(mode == "interpret"))
        return jax.lax.top_k(parts.reshape(-1), k)[0][k - 1]


def _topk_threshold_impl(x, *, k: int, mode: str):
    return _threshold_impl(x, k, mode)


_topk_threshold = jax.jit(_topk_threshold_impl,
                          static_argnames=("k", "mode"))


def topk_threshold(x: jnp.ndarray, k: int, *,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """The TopK mask threshold: the k-th largest |x| (ties inclusive)."""
    mode = registry.resolve_mode("topk_partials", interpret)
    return _topk_threshold(x, k=int(k), mode=mode)


def _top_k_compress_impl(x, *, k: int, tmode: str, imask: bool):
    thresh = _threshold_impl(x, k, tmode)
    x2d, n = _to_2d(x)
    _STATS["pallas_calls"] += 1
    out = _topk.topk_mask_2d(x2d, thresh.reshape(1, 1), interpret=imask)
    return _from_2d(out, n, x.shape, x.dtype)


_top_k_compress = jax.jit(_top_k_compress_impl,
                          static_argnames=("k", "tmode", "imask"))


def top_k_compress(x: jnp.ndarray, k: int, *,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Keep the k largest-|.| coordinates of ``x``, zero the rest —
    BITWISE-equal to ``repro.core.compression.TopK`` (same threshold,
    same inclusive tie handling), on every shape/dtype the parity suite
    sweeps."""
    tmode = registry.resolve_mode("topk_partials", interpret)
    imask = registry.resolve_interpret("topk_mask", interpret)
    return _top_k_compress(x, k=int(k), tmode=tmode, imask=imask)


# ---------------------------------------------------------------------------
# Fused CHOCO compress-and-move
# ---------------------------------------------------------------------------


def _fused_diff(x, y, mixed_y, g32):
    """The compressed gap diff = (x + gamma (my - y)) - y, flat, in the
    LEAF dtype — exactly the tensor the unfused path materializes and
    hands to the compressor (so thresholds/norms computed on it match the
    unfused kernels bit-for-bit)."""
    x32 = x.reshape(-1).astype(jnp.float32)
    y32 = y.reshape(-1).astype(jnp.float32)
    my32 = mixed_y.reshape(-1).astype(jnp.float32)
    return ((x32 + g32 * (my32 - y32)) - y32).astype(x.dtype)


def _choco_qsgd_move_impl(x, y, mixed_y, gamma, noise, *, levels: int,
                          interpret: bool):
    d = x.size
    s = float(levels)
    c = 1.0 + min(d / (s * s), (d ** 0.5) / s)
    g32 = jnp.asarray(gamma, jnp.float32)
    diff = _fused_diff(x, y, mixed_y, g32)
    norm = jnp.linalg.norm(diff.astype(jnp.float32))
    scal = jnp.stack([g32, norm]).reshape(1, 2)
    x2d, n = _to_2d(x)
    y2d, _ = _to_2d(y)
    my2d, _ = _to_2d(mixed_y)
    n2d, _ = _to_2d(noise)
    _STATS["pallas_calls"] += 1
    xo, yo = _fused.choco_qsgd_2d(x2d, y2d, my2d, n2d, scal, levels=levels,
                                  c=c, interpret=interpret)
    return (_from_2d(xo, n, x.shape, x.dtype),
            _from_2d(yo, n, x.shape, x.dtype))


_choco_qsgd_move = jax.jit(_choco_qsgd_move_impl,
                           static_argnames=("levels", "interpret"))


def choco_qsgd_move(x: jnp.ndarray, y: jnp.ndarray, mixed_y: jnp.ndarray,
                    gamma, noise: jnp.ndarray, *, levels: int = 16,
                    interpret: Optional[bool] = None):
    """Fused CHOCO step with QSGD compression: ONE kernel pass over
    (x, y, mixed_y, noise) emitting (x_new, y_new) — vs the unfused
    choco_move -> qsgd_quantize -> XLA-add chain (3 padded round-trips,
    2 kernel launches, 2 HBM intermediates)."""
    interpret = registry.resolve_interpret("choco_qsgd", interpret)
    return _choco_qsgd_move(x, y, mixed_y, gamma, noise, levels=levels,
                            interpret=interpret)


def _choco_topk_move_impl(x, y, mixed_y, gamma, *, k: int, tmode: str,
                          interpret: bool):
    g32 = jnp.asarray(gamma, jnp.float32)
    diff = _fused_diff(x, y, mixed_y, g32)
    if not 1 <= k <= diff.size:
        raise ValueError(
            f"TopK k={k} out of range for a size-{diff.size} vector")
    # ONE pad round-trip for the gap: the padded diff feeds both the
    # candidate select and the mask input of the fused kernel, so the
    # threshold and the kept-set decisions read the identical tensor.
    d2d, n = _to_2d(diff)
    with jax.named_scope("compress.threshold"):
        if tmode == "fallback":
            thresh = _kth_largest_magnitude(diff, k)
        else:
            cand = min(k, _TILE)
            _STATS["pallas_calls"] += 1
            parts = _topk.topk_partials_2d(d2d, cand=cand,
                                           interpret=(tmode == "interpret"))
            thresh = jax.lax.top_k(parts.reshape(-1), k)[0][k - 1]
    x2d, _ = _to_2d(x)
    y2d, _ = _to_2d(y)
    my2d, _ = _to_2d(mixed_y)
    _STATS["pallas_calls"] += 1
    xo, yo = _fused.choco_topk_2d(x2d, y2d, my2d, d2d, g32.reshape(1, 1),
                                  thresh.reshape(1, 1), interpret=interpret)
    return (_from_2d(xo, n, x.shape, x.dtype),
            _from_2d(yo, n, x.shape, x.dtype))


_choco_topk_move = jax.jit(_choco_topk_move_impl,
                           static_argnames=("k", "tmode", "interpret"))


def choco_topk_move(x: jnp.ndarray, y: jnp.ndarray, mixed_y: jnp.ndarray,
                    gamma, k: int, *, interpret: Optional[bool] = None):
    """Fused CHOCO step with TopK compression: the threshold select reads
    the gap once (reduction to one scalar), then ONE kernel pass emits
    (x_new, y_new) — vs choco_move -> top_k_compress -> XLA-add."""
    tmode = registry.resolve_mode("topk_partials", interpret)
    interp = registry.resolve_interpret("choco_topk", interpret)
    return _choco_topk_move(x, y, mixed_y, gamma, k=int(k), tmode=tmode,
                            interpret=interp)


# ---------------------------------------------------------------------------
# Instrumentation access
# ---------------------------------------------------------------------------

_EAGER_IMPLS = {
    "qsgd_quantize": _qsgd_quantize_impl,
    "gossip_mix": _gossip_mix_impl,
    "choco_move": _choco_move_impl,
    "topk_threshold": _topk_threshold_impl,
    "top_k_compress": _top_k_compress_impl,
    "choco_qsgd_move": _choco_qsgd_move_impl,
    "choco_topk_move": _choco_topk_move_impl,
}


def eager_impl(name: str):
    """The UN-JITTED wrapper body behind a public entry point, for
    instrumentation: calling it executes the Python body every time, so
    the ``op_stats`` counters tick deterministically per call (the jitted
    publics only tick per trace). Callers pass the dispatch statics
    explicitly (``interpret=True`` / ``tmode="interpret"`` etc.) — no
    registry resolution happens here. Used by ``benchmarks/bench_kernels``
    to count fused-vs-unfused buffer passes; not a performance surface."""
    try:
        return _EAGER_IMPLS[name]
    except KeyError:
        raise ValueError(
            f"no eager impl {name!r}; options: {sorted(_EAGER_IMPLS)}"
        ) from None
