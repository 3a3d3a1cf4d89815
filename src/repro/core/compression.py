"""Compression operators Q for C-DFL (paper Sec. V-A).

Each operator satisfies Assumption 2:  E_Q ||Q(x) - x||^2 <= (1 - delta) ||x||^2
with compression ratio delta in (0, 1]. Operators act leaf-wise on pytrees
(each leaf treated as one vector x in R^d, matching the paper's per-model
compression) and return a *dense* array with the compression applied — the
paper's own simulation does the same; actual wire savings are accounted
analytically via ``bits_per_value`` / ``wire_bits``.

Operators implemented (paper Sec. V-A list):
  * ``TopK``            — k = ceil(frac * d) largest-magnitude coords.
  * ``RandK``           — k random coords (unbiased up to scaling; the plain
                          projected version used by CHOCO satisfies Asm. 2).
  * ``QSGD``            — random s-level quantization, rescaled (delta = 1/c).
  * ``RandomizedGossip``— Q(x) = x w.p. p else 0 (delta = p).
  * ``Identity``        — delta = 1 (plain DFL).

The QSGD and TopK hot loops have Pallas TPU kernels in ``repro.kernels``;
this module is the pure-jnp reference implementation used by the algorithm
layer (and as the kernels' oracle). ``TopK(use_kernels=True)`` (or
``make_compressor("top_k", use_kernels=True)``) routes ``__call__``
through the kernel-backed two-pass select+mask (``ops.top_k_compress``),
which is BITWISE-equal to the reference here — threshold, inclusive tie
handling and all — so flipping the flag never changes trajectories. The
sharded engine's ``use_kernels`` hot path additionally fuses compression
into the CHOCO move (``substrate.ShardedSubstrate.choco_step``); see
docs/ARCHITECTURE.md for the dispatch path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

__all__ = [
    "Compressor",
    "Identity",
    "TopK",
    "RandK",
    "QSGD",
    "RandomizedGossip",
    "make_compressor",
    "compress_tree",
    "tree_wire_bits",
]


@dataclasses.dataclass(frozen=True)
class Compressor:
    """Base compression operator."""

    name: str = "identity"

    def delta(self, d: int) -> float:
        """Compression ratio delta of Assumption 2 for dimension d."""
        return 1.0

    def bits_per_value(self, d: int) -> float:
        """Average wire bits per *original* coordinate (fp32 baseline = 32)."""
        return 32.0

    def __call__(self, x: jnp.ndarray, key: Optional[jax.Array]) -> jnp.ndarray:
        return x


@dataclasses.dataclass(frozen=True)
class Identity(Compressor):
    name: str = "identity"


@dataclasses.dataclass(frozen=True)
class TopK(Compressor):
    """Keep the ceil(frac*d) largest-|.| coordinates; zero the rest.

    ``delta = k/d`` (Assumption 2 holds with equality in the worst case);
    wire cost is value + index bits per kept coordinate. ``use_kernels``
    dispatches ``__call__`` to the Pallas two-pass kernel
    (``repro.kernels.ops.top_k_compress``) — bitwise-identical output
    (same k-th-largest threshold, same inclusive tie handling), kernel
    tiling off the hot loop's critical path on TPU.
    """

    name: str = "top_k"
    frac: float = 0.5
    use_kernels: bool = False

    def _k(self, d: int) -> int:
        return max(1, int(np.ceil(self.frac * d)))

    def delta(self, d: int) -> float:
        return self._k(d) / d

    def bits_per_value(self, d: int) -> float:
        # value + index per kept coordinate.
        k = self._k(d)
        return (32.0 + np.ceil(np.log2(max(d, 2)))) * k / d

    def __call__(self, x: jnp.ndarray, key: Optional[jax.Array]) -> jnp.ndarray:
        flat = x.reshape(-1)
        k = self._k(flat.size)
        if self.use_kernels:
            from repro.kernels import ops as kernel_ops

            return kernel_ops.top_k_compress(x, k)
        # threshold = k-th largest magnitude; ties keep >= threshold (may keep
        # a few extra ties — still satisfies Assumption 2).
        with jax.named_scope("compress.threshold"):
            thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
        kept = jnp.where(jnp.abs(flat) >= thresh, flat, 0.0)
        return kept.reshape(x.shape).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class RandK(Compressor):
    """Keep k = ceil(frac*d) uniformly random coordinates."""

    name: str = "rand_k"
    frac: float = 0.5

    def _k(self, d: int) -> int:
        return max(1, int(np.ceil(self.frac * d)))

    def delta(self, d: int) -> float:
        return self._k(d) / d

    def bits_per_value(self, d: int) -> float:
        # shared PRNG seed => only values travel.
        return 32.0 * self._k(d) / d

    def __call__(self, x: jnp.ndarray, key: Optional[jax.Array]) -> jnp.ndarray:
        assert key is not None, "RandK requires a PRNG key"
        flat = x.reshape(-1)
        k = self._k(flat.size)
        scores = jax.random.uniform(key, flat.shape)
        thresh = jax.lax.top_k(scores, k)[0][-1]
        kept = jnp.where(scores >= thresh, flat, 0.0)
        return kept.reshape(x.shape).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class QSGD(Compressor):
    """Random quantization qsgd_s (paper eq. in Sec. V-A), rescaled by 1/c so
    that Assumption 2 holds with delta = 1/c, c = 1 + min(d/s^2, sqrt(d)/s).
    """

    name: str = "qsgd"
    levels: int = 16  # s

    def _c(self, d: int) -> float:
        s = float(self.levels)
        return 1.0 + min(d / (s * s), np.sqrt(d) / s)

    def delta(self, d: int) -> float:
        return 1.0 / self._c(d)

    def bits_per_value(self, d: int) -> float:
        # sign + level index per coordinate + one fp32 norm per vector.
        return 1.0 + np.ceil(np.log2(self.levels + 1)) + 32.0 / d

    def __call__(self, x: jnp.ndarray, key: Optional[jax.Array]) -> jnp.ndarray:
        assert key is not None, "QSGD requires a PRNG key"
        flat = x.reshape(-1).astype(jnp.float32)
        d = flat.size
        s = float(self.levels)
        norm = jnp.linalg.norm(flat)
        xi = jax.random.uniform(key, flat.shape)
        safe = jnp.where(norm > 0, norm, 1.0)
        lvl = jnp.floor(s * jnp.abs(flat) / safe + xi)
        q = jnp.sign(flat) * safe * lvl / (s * self._c(d))
        q = jnp.where(norm > 0, q, 0.0)
        return q.reshape(x.shape).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class RandomizedGossip(Compressor):
    """Q(x) = x with probability p else 0 (per vector); delta = p."""

    name: str = "rand_gossip"
    p: float = 0.8

    def delta(self, d: int) -> float:
        return self.p

    def bits_per_value(self, d: int) -> float:
        return 32.0 * self.p

    def __call__(self, x: jnp.ndarray, key: Optional[jax.Array]) -> jnp.ndarray:
        assert key is not None, "RandomizedGossip requires a PRNG key"
        keep = jax.random.bernoulli(key, self.p)
        return jnp.where(keep, x, jnp.zeros_like(x))


_REGISTRY = {
    "identity": Identity,
    "top_k": TopK,
    "rand_k": RandK,
    "qsgd": QSGD,
    "rand_gossip": RandomizedGossip,
}


def make_compressor(name: str, **kwargs) -> Compressor:
    """Build a registered compressor by name with its dataclass kwargs.

    Names: "identity", "top_k" (``frac``, ``use_kernels``), "rand_k"
    (``frac``), "qsgd" (``levels``), "rand_gossip" (``p``). The planner
    (``repro.planner.cost``) prices wire bits through the instance's
    ``bits_per_value``/``delta`` contracts (see docs/THEORY.md).
    """
    try:
        return _REGISTRY[name](**kwargs)
    except KeyError:
        raise ValueError(
            f"unknown compressor {name!r}; options: {sorted(_REGISTRY)}"
        ) from None


def compress_tree(comp: Compressor, tree: PyTree, key: Optional[jax.Array]) -> PyTree:
    """Apply Q leaf-wise with independent fold_in'ed keys per leaf."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = (
        [None] * len(leaves)
        if key is None
        else list(jax.random.split(key, max(len(leaves), 1)))
    )
    out = [comp(leaf, k) for leaf, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_wire_bits(comp: Compressor, tree: PyTree) -> float:
    """Total wire bits to transmit one compressed copy of ``tree``."""
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        d = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += comp.bits_per_value(d) * d
    return total
