"""Plain float32 reference of the benchmark's language models.

Written from the configuration file alone: it imports nothing of the
program under test and takes none of its arrays. The weights are the
benchmark's own seeded draw (``make_weights``), named by the flat keys the
program's parameter tree flattens to (``embed``, ``final_norm``,
``lm_head``, ``blocks.<position>.<layer key>``, each block leaf stacked
over the pattern's periods). Every layer kind of the pattern (a mixer or
ffn name) has its own file beside this one, ``bench/reference/<kind>.py``,
with ``params(model)`` and ``apply(p, h, pos, model, ops)``.

Matrix products go through ``Ops.mm``: at ``precision="float32"`` they run
in float32 at ``Precision.HIGHEST``; at ``precision="fp8"`` (the control)
both operands and, in the backward pass, the incoming gradient are rounded
to float8 e4m3 with one scale per tensor, the step below the bfloat16 the
configurations state. The stored parameters keep the configuration's
dtype in both.
"""
from __future__ import annotations

import math
import os
import zlib
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.spec import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

Spec = Tuple[Tuple[int, ...], str, Tuple[str, float]]   # shape, dtype, init


def dtype_of(name: str, model: Dict[str, Any]):
    return jnp.dtype(model["dtype"] if name == "param" else name)


def _load_kind(kind: str):
    return load_module(os.path.join(HERE, f"{kind}.py"), f"bench_ref_{kind}")


def kinds_of(model: Dict[str, Any]) -> List[List[Tuple[str, Any]]]:
    """Per pattern position: the (kind, module) pairs applied in order."""
    out = []
    for spec in model["pattern"]:
        mods = []
        for kind in (spec.get("mixer", "attn"), spec.get("ffn", "mlp")):
            if kind != "none":
                mods.append((kind, _load_kind(kind)))
        out.append(mods)
    return out


def padded_vocab(v: int) -> int:
    return int(math.ceil(v / 128) * 128)


def periods(model: Dict[str, Any]) -> int:
    return model["num_layers"] // len(model["pattern"])


def param_specs(model: Dict[str, Any]) -> Dict[str, Spec]:
    """Every weight: flat name -> (shape, dtype, init). ``init`` is
    ("normal", std), ("zeros", 0), ("ones", 0) or ("log_arange", 0)."""
    d, vp = model["d_model"], padded_vocab(model["vocab_size"])
    specs: Dict[str, Spec] = {
        "embed": ((vp, d), "param", ("normal", 0.02)),
        "final_norm": ((d,), "param", ("zeros", 0.0)),
    }
    if not model["tie_embeddings"]:
        specs["lm_head"] = ((d, vp), "param", ("normal", d ** -0.5))
    n_per = periods(model)
    for slot, mods in enumerate(kinds_of(model)):
        for _kind, mod in mods:
            for key, (shape, dt, init) in mod.params(model).items():
                specs[f"blocks.{slot}.{key}"] = ((n_per,) + tuple(shape),
                                                 dt, init)
    return specs


def make_weights(specs: Dict[str, Spec], model: Dict[str, Any],
                 key: jax.Array) -> Dict[str, jax.Array]:
    """The seeded weights (traceable: run it under one ``jax.jit``). Each
    leaf draws from ``fold_in(key, crc32(name))``, so a leaf's values do
    not depend on which other leaves exist."""
    out = {}
    for name, (shape, dt, (init, std)) in specs.items():
        dtype = dtype_of(dt, model)
        if init == "normal":
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            v = jax.random.normal(k, shape, jnp.float32) * std
        elif init == "zeros":
            v = jnp.zeros(shape, jnp.float32)
        elif init == "ones":
            v = jnp.ones(shape, jnp.float32)
        elif init == "log_arange":
            v = jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
                shape)
        else:
            raise ValueError(f"unknown init {init!r} of {name}")
        out[name] = v.astype(dtype)
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits of it."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def _quantize(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _fp8_einsum(eq: str):
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(eq, _quantize(a), _quantize(b),
                          precision=jax.lax.Precision.HIGHEST)

    def fwd(a, b):
        qa, qb = _quantize(a), _quantize(b)
        return jnp.einsum(eq, qa, qb,
                          precision=jax.lax.Precision.HIGHEST), (qa, qb)

    def bwd(res, g):
        qa, qb = res
        _, vjp = jax.vjp(lambda x, y: jnp.einsum(
            eq, x, y, precision=jax.lax.Precision.HIGHEST), qa, qb)
        return vjp(_quantize(g))

    f.defvjp(fwd, bwd)
    return f


class Ops:
    """The reference's arithmetic at one precision."""

    def __init__(self, precision: str = "float32", rms_eps: float = 1e-6):
        if precision not in ("float32", "fp8"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.precision = precision
        self.rms_eps = rms_eps
        self._fp8: Dict[str, Callable] = {}

    def mm(self, eq: str, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.precision == "float32":
            return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
        if eq not in self._fp8:
            self._fp8[eq] = _fp8_einsum(eq)
        return self._fp8[eq](a, b)

    def rms(self, x, scale):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.rms_eps) * (1.0 + scale)


def layer_params(w: Dict[str, jax.Array], slot: int, i: int
                 ) -> Dict[str, jax.Array]:
    """Period ``i``'s weights of pattern position ``slot``, in float32."""
    prefix = f"blocks.{slot}."
    return {k[len(prefix):]: v[i].astype(jnp.float32)
            for k, v in w.items() if k.startswith(prefix)}


def loss(w: Dict[str, jax.Array], tokens, labels, pos, model: Dict[str, Any],
         ops: Ops, row_block: int = 512):
    """Mean next-token cross-entropy over every position of the batch
    ([B, S] tokens at positions ``pos``, [S]), over the (padded) vocabulary
    rows the weights hold. Each layer, and each block of rows of the loss,
    is recomputed in the backward pass, so the reference fits beside the
    stored weights. ``pos`` is an argument, not a constant, so that the
    compiler does not fold position tables and masks into the program."""
    kinds = kinds_of(model)
    embed = w["embed"].astype(jnp.float32)
    h = embed[tokens]
    for i in range(periods(model)):
        for slot, mods in enumerate(kinds):
            p = layer_params(w, slot, i)
            for _kind, mod in mods:
                h = jax.checkpoint(
                    lambda hh, pp, mod=mod: mod.apply(pp, hh, pos, model,
                                                      ops))(h, p)
    h = ops.rms(h, w["final_norm"].astype(jnp.float32))
    head = (embed.T if model["tie_embeddings"]
            else w["lm_head"].astype(jnp.float32))
    b, s, d = h.shape
    rb = min(row_block, s)
    while s % rb:
        rb -= 1

    def block_loss(hb, lb, head):
        logits = ops.mm("bsd,dv->bsv", hb, head)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - gold)

    total = 0.0
    for j in range(s // rb):
        total = total + jax.checkpoint(block_loss)(
            h[:, j * rb:(j + 1) * rb], labels[:, j * rb:(j + 1) * rb], head)
    return total / (b * s)


