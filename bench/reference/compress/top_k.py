"""Reference TopK compressor (Koloskova et al. 2019, arXiv:1902.00340):
of one leaf's float32 difference, keep the k = ceil(frac d) largest
magnitudes, ties at the k-th kept, and zero the rest."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def kth_largest(x, k: int):
    """The k-th largest of the non-negative float32 ``x``, exactly: a
    bisection over the float's bit pattern, which orders non-negative
    floats as integers."""
    bits = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = jnp.sum((bits >= mid).astype(jnp.int32)) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, 32, body, (jnp.int32(0),
                                            jnp.int32(0x7F800001)))
    return jax.lax.bitcast_convert_type(lo, jnp.float32)


def compress(diff, frac: float):
    k = max(1, int(np.ceil(frac * diff.size)))
    keep = jnp.abs(diff) >= kth_largest(jnp.abs(diff), k)
    return jnp.where(keep, diff, 0.0)
