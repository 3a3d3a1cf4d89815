"""Compression/gossip kernel subsystem (Pallas TPU + jnp oracles).

The per-byte hot loops of the paper's communication stage — quantize,
sparsify, mix, CHOCO error-feedback — as Pallas kernels with a registry
that decides, per op and per call, whether to run Mosaic-compiled (TPU),
interpret-mode (validation, the off-TPU default), or a plain-XLA fallback
(ops Mosaic cannot lower). Layout:

  ``registry``     — lazy backend detection, per-op dispatch guards,
                     the reference-parity harness (start here).
  ``ops``          — the public entry points (pad/tile/unpad handling,
                     jitted): ``qsgd_quantize``, ``gossip_mix``,
                     ``choco_move``, ``top_k_compress``,
                     ``topk_threshold``, ``choco_qsgd_move``,
                     ``choco_topk_move``.
  ``qsgd`` / ``gossip_mix`` / ``choco_update`` / ``topk`` /
  ``choco_fused`` — the kernel bodies (tile shapes, BlockSpecs).
  ``ref``          — pure-jnp oracles, one per kernel, the source of
                     truth for the parity suite.

Consumers: ``repro.core.substrate.ShardedSubstrate`` (``use_kernels=True``
routes gossip/CHOCO through here), ``repro.core.compression.TopK``
(``use_kernels=True`` field), ``benchmarks/bench_kernels`` (parity +
throughput + buffer-pass accounting). See docs/ARCHITECTURE.md for the
dispatch path end-to-end.
"""
from repro.kernels.ops import (
    choco_move,
    choco_qsgd_move,
    choco_topk_move,
    gossip_mix,
    op_stats,
    op_stats_delta,
    qsgd_quantize,
    top_k_compress,
    topk_threshold,
)
from repro.kernels.registry import (
    KernelOp,
    backend,
    get_op,
    list_ops,
    on_tpu,
    parity_suite,
    reset_backend_cache,
    resolve_interpret,
    resolve_mode,
)

__all__ = [
    "backend",
    "on_tpu",
    "reset_backend_cache",
    "KernelOp",
    "get_op",
    "list_ops",
    "resolve_mode",
    "resolve_interpret",
    "parity_suite",
    "qsgd_quantize",
    "gossip_mix",
    "choco_move",
    "topk_threshold",
    "top_k_compress",
    "choco_qsgd_move",
    "choco_topk_move",
    "op_stats",
    "op_stats_delta",
]
