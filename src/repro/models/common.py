"""Shared model-definition machinery.

Models are pure functions over pytree params. Every parameter is created
through ``Param`` helpers that record *logical axis names* alongside the
array; the launcher maps logical axes to mesh axes (see
``repro/launch/sharding_rules.py``). This mirrors MaxText's logical-axis
design without depending on flax.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer inside the repeating period block of a stack."""

    mixer: str = "attn"          # attn | mla | mamba
    ffn: str = "mlp"             # mlp | moe | none
    window: int = 0              # sliding-window size; 0 = full attention
    cross_attn: bool = False     # adds a cross-attention sub-block
    rope_theta: float = 0.0      # 0 = use model default


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters, generic over the 6 assigned families."""

    name: str
    arch_type: str               # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int              # decoder layers (excludes encoder_layers)
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # attention
    qk_norm: bool = False
    rope_theta: float = 1e4
    logit_softcap: float = 0.0
    rms_eps: float = 1e-6
    # latent attention (mixer "mla", DeepSeek-V2/V3): q and k have
    # qk_nope_head_dim + qk_rope_head_dim dims, v has v_head_dim; k's rope
    # part is one head shared by all heads; k_nope and v come from a
    # kv_lora_rank-dim compressed kv
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # repeating layer pattern; default = uniform (attn + cfg-default ffn)
    pattern: Tuple[LayerSpec, ...] = ()
    # MoE: the router scores router_experts (0 = num_experts) and picks
    # experts_per_token; this layer holds and computes the first
    # num_experts of them, each of width expert_d_ff (0 = d_ff)
    num_experts: int = 0
    experts_per_token: int = 0
    router_experts: int = 0
    expert_d_ff: int = 0
    num_shared_experts: int = 0
    router_scoring: str = "softmax"      # softmax | sigmoid
    routed_scaling: float = 1.0
    router_aux_coef: float = 0.01
    # SSM (mamba-1)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: int = 0             # 0 = ceil(d_model / 16)
    # encoder-decoder / multimodal
    encoder_layers: int = 0      # >0 => enc-dec (audio); encoder is bidirectional
    memory_tokens: int = 0       # VLM patches / audio frames expected (spec hint)
    memory_dim: int = 0          # frontend embedding dim (stub); 0 = d_model
    # embeddings / numerics
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    # training-time mechanics
    scan_layers: bool = True
    remat: bool = True
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 1024
    decode_unchunked: bool = False   # perf variant: single-block decode attn
    loss_seq_chunk: int = 512
    ssm_chunk: int = 128
    # attention sharding family: heads | head_dim | replicated
    attn_shard: str = "heads"
    # provenance
    citation: str = ""

    def __post_init__(self):
        if self.pattern == ():
            ffn = "moe" if self.num_experts > 0 else "mlp"
            mixer = "mamba" if self.arch_type == "ssm" else "attn"
            object.__setattr__(self, "pattern", (LayerSpec(mixer=mixer, ffn=ffn),))
        assert self.num_layers % len(self.pattern) == 0, (
            f"{self.name}: num_layers {self.num_layers} not divisible by "
            f"pattern period {len(self.pattern)}"
        )

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def num_periods(self) -> int:
        return self.num_layers // self.period

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def expert_width(self) -> int:
        return self.expert_d_ff or self.d_ff

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank_actual(self) -> int:
        return self.dt_rank or int(math.ceil(self.d_model / 16))

    @property
    def is_enc_dec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def has_memory_input(self) -> bool:
        return self.arch_type in ("vlm", "audio")

    def layer_specs(self) -> List[LayerSpec]:
        return list(self.pattern) * self.num_periods

    def param_count(self) -> int:
        """Total parameter count (exact, from the init shapes)."""
        from repro.models.transformer import init_params  # cycle-free at call

        params, _ = init_params(self, jax.random.key(0), abstract=True)
        leaves = jax.tree_util.tree_leaves(params)
        return sum(int(np.prod(l.shape)) for l in leaves)

    def active_param_count(self) -> int:
        """Active params per token (MoE discounts inactive experts)."""
        total = self.param_count()
        if self.num_experts == 0:
            return total
        # expert weights: ffn mlp tensors in moe layers.
        specs = self.layer_specs()
        n_moe = sum(1 for s in specs if s.ffn == "moe")
        per_expert = 3 * self.d_model * self.expert_width
        expert_total = n_moe * self.num_experts * per_expert
        expert_active = n_moe * self.experts_per_token * per_expert
        return total - expert_total + expert_active


# ---------------------------------------------------------------------------
# Params with logical axes
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Annotated:
    """A parameter leaf paired with its logical-axis names."""

    value: Any
    axes: Tuple[Optional[str], ...]


class ParamFactory:
    """Creates ``Annotated`` params; ``split_annotations`` separates the
    value tree from the logical-axes tree afterwards."""

    def __init__(self, key: jax.Array, dtype, abstract: bool = False):
        self._key = key
        self._dtype = dtype
        self._abstract = abstract

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def dense(self, shape: Sequence[int], axes: Sequence[Optional[str]],
              scale: Optional[float] = None) -> Annotated:
        assert len(shape) == len(axes), (shape, axes)
        fan_in = shape[0] if len(shape) > 1 else shape[0]
        std = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
        if self._abstract:
            v = jax.ShapeDtypeStruct(tuple(shape), self._dtype)
        else:
            v = (
                jax.random.normal(self._next_key(), tuple(shape), jnp.float32) * std
            ).astype(self._dtype)
        return Annotated(v, tuple(axes))

    def zeros(self, shape: Sequence[int], axes: Sequence[Optional[str]],
              dtype=None) -> Annotated:
        dt = dtype or self._dtype
        if self._abstract:
            v = jax.ShapeDtypeStruct(tuple(shape), dt)
        else:
            v = jnp.zeros(tuple(shape), dt)
        return Annotated(v, tuple(axes))

    def ones(self, shape: Sequence[int], axes: Sequence[Optional[str]],
             dtype=None) -> Annotated:
        dt = dtype or self._dtype
        if self._abstract:
            v = jax.ShapeDtypeStruct(tuple(shape), dt)
        else:
            v = jnp.ones(tuple(shape), dt)
        return Annotated(v, tuple(axes))

    def const(self, value: np.ndarray, axes: Sequence[Optional[str]]) -> Annotated:
        if self._abstract:
            v = jax.ShapeDtypeStruct(np.asarray(value).shape, jnp.float32)
        else:
            v = jnp.asarray(value, jnp.float32)
        return Annotated(v, tuple(axes))


def split_annotations(tree: PyTree) -> Tuple[PyTree, PyTree]:
    """Split a tree of ``Annotated`` into (values, logical_axes) trees."""
    is_ann = lambda x: isinstance(x, Annotated)
    values = jax.tree_util.tree_map(lambda a: a.value, tree, is_leaf=is_ann)
    axes = jax.tree_util.tree_map(lambda a: a.axes, tree, is_leaf=is_ann)
    return values, axes


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. x: [..., S, H, hd]; positions: [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # [..., S, half]
    sin = jnp.sin(ang)[..., None, :]  # broadcast over heads
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    g = jnp.einsum("...d,df->...f", x, w_gate.astype(x.dtype))
    u = jnp.einsum("...d,df->...f", x, w_up.astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down.astype(x.dtype))


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


def pad_vocab(v: int, multiple: int = 128) -> int:
    return int(math.ceil(v / multiple) * multiple)
