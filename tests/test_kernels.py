"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

SHAPES = [(64,), (1000,), (256, 128), (3, 5, 7), (32768,), (300, 70)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_qsgd_matches_ref(shape, dtype):
    k1, k2 = jax.random.split(jax.random.key(hash(shape) % 2**31))
    x = (jax.random.normal(k1, shape, jnp.float32) * 3).astype(dtype)
    noise = jax.random.uniform(k2, shape)
    d = int(np.prod(shape))
    s = 16.0
    c = 1.0 + min(d / (s * s), d**0.5 / s)
    got = ops.qsgd_quantize(x, noise, levels=16, interpret=True)
    want = ref.qsgd_ref(x, noise, levels=16, c=c)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("deg", [1, 2, 4])
def test_gossip_mix_matches_ref(shape, deg):
    key = jax.random.key(deg)
    x = jax.random.normal(jax.random.fold_in(key, 0), shape)
    nbrs = jax.random.normal(jax.random.fold_in(key, 1), (deg,) + shape)
    w = jnp.concatenate([jnp.asarray([0.5]),
                         jnp.full((deg,), 0.5 / deg)])
    got = ops.gossip_mix(x, nbrs, w, interpret=True)
    want = ref.gossip_mix_ref(x, nbrs, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_choco_move_matches_ref(shape, dtype):
    key = jax.random.key(7)
    x = jax.random.normal(jax.random.fold_in(key, 0), shape).astype(dtype)
    y = jax.random.normal(jax.random.fold_in(key, 1), shape).astype(dtype)
    my = jax.random.normal(jax.random.fold_in(key, 2), shape).astype(dtype)
    xg, dg = ops.choco_move(x, y, my, 0.37, interpret=True)
    xw, dw = ref.choco_move_ref(x, y, my, 0.37)
    # bf16 outputs can differ by one ulp from rounding order.
    tol = 1e-4 if dtype == jnp.float32 else 8e-3
    np.testing.assert_allclose(np.asarray(xg, np.float32),
                               np.asarray(xw, np.float32), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(dg, np.float32),
                               np.asarray(dw, np.float32), rtol=tol,
                               atol=tol)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 5000), st.integers(0, 2**31 - 1))
def test_qsgd_property_random_sizes(n, seed):
    """Property sweep: arbitrary vector lengths (padding path) match ref."""
    k1, k2 = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(k1, (n,))
    noise = jax.random.uniform(k2, (n,))
    s = 8.0
    c = 1.0 + min(n / (s * s), n**0.5 / s)
    got = ops.qsgd_quantize(x, noise, levels=8, interpret=True)
    want = ref.qsgd_ref(x, noise, levels=8, c=c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_qsgd_kernel_agrees_with_library_compressor():
    """The kernel implements the same Q as core.compression.QSGD (same
    noise => identical output)."""
    from repro.core.compression import QSGD

    n = 4096
    x = jax.random.normal(jax.random.key(0), (n,))
    key = jax.random.key(42)
    noise = jax.random.uniform(key, (n,))
    got = ops.qsgd_quantize(x, noise, levels=16, interpret=True)

    # re-derive library output with identical noise by monkey-path-free math
    want = ref.qsgd_ref(x, noise, levels=16,
                        c=1.0 + min(n / 256.0, n**0.5 / 16.0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    comp = QSGD(levels=16)
    assert abs(comp.delta(n) - 1.0 / (1.0 + min(n / 256.0, n**0.5 / 16.0))) < 1e-12


# ---------------------------------------------------------------------------
# TopK kernel (two-pass candidate select + mask)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_topk_bitwise_matches_ref(shape, dtype):
    x = jax.random.normal(
        jax.random.key(hash(shape) % 2**31), shape).astype(dtype)
    for k in {1, max(1, int(np.prod(shape)) // 3), int(np.prod(shape))}:
        got = ops.top_k_compress(x, k, interpret=True)
        want = ref.top_k_ref(x, k)
        assert jnp.array_equal(got, want), (shape, dtype, k)


def test_topk_matches_library_compressor_bitwise():
    """TopK(use_kernels=True) is the SAME operator as the reference
    TopK — flipping the flag can never change a trajectory."""
    from repro.core.compression import TopK

    for shape in [(1000,), (300, 70), (32769,)]:
        x = jax.random.normal(jax.random.key(3), shape)
        for frac in (0.01, 0.25, 1.0):
            want = TopK(frac=frac)(x, None)
            got = TopK(frac=frac, use_kernels=True)(x, None)
            assert jnp.array_equal(got, want), (shape, frac)


def test_topk_tie_handling():
    """Ties AT the threshold are kept inclusively, exactly like the
    reference (which may keep more than k coordinates)."""
    x = jnp.asarray([2.0, -2.0, 2.0, 0.5, -0.25, 2.0, 0.0, -2.0, 1.0])
    for k in range(1, x.size + 1):
        got = ops.top_k_compress(x, k, interpret=True)
        want = ref.top_k_ref(x, k)
        assert jnp.array_equal(got, want), k
    # all-tied vector: any k keeps everything
    t = jnp.full((300,), -1.5)
    assert jnp.array_equal(ops.top_k_compress(t, 7, interpret=True), t)


def test_topk_k_equals_d_is_identity():
    x = jax.random.normal(jax.random.key(5), (257,))
    assert jnp.array_equal(ops.top_k_compress(x, 257, interpret=True), x)


def test_topk_zero_vector_and_threshold_zero():
    z = jnp.zeros((100,))
    assert jnp.array_equal(ops.top_k_compress(z, 10, interpret=True), z)
    # true threshold 0: zeros padding can't perturb the selection
    x = jnp.concatenate([jnp.asarray([3.0, -2.0]), jnp.zeros((98,))])
    got = ops.top_k_compress(x, 50, interpret=True)
    assert jnp.array_equal(got, ref.top_k_ref(x, 50))


def test_topk_out_of_range_k_raises():
    x = jnp.ones((8,))
    with pytest.raises(ValueError, match="out of range"):
        ops.top_k_compress(x, 9, interpret=True)
    with pytest.raises(ValueError, match="out of range"):
        ops.top_k_compress(x, 0, interpret=True)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 5000), st.integers(0, 2**31 - 1))
def test_topk_property_random_sizes(n, seed):
    """Non-tile-multiple sizes (padding path) stay bitwise vs ref."""
    key = jax.random.key(seed)
    x = jax.random.normal(key, (n,))
    k = 1 + seed % n
    got = ops.top_k_compress(x, k, interpret=True)
    want = ref.top_k_ref(x, k)
    assert jnp.array_equal(got, want), (n, k)


# ---------------------------------------------------------------------------
# Fused CHOCO compress-and-move
# ---------------------------------------------------------------------------


def _fused_inputs(shape, dtype, seed=5):
    key = jax.random.key(seed)
    x, y, my = (jax.random.normal(jax.random.fold_in(key, i),
                                  shape).astype(dtype) for i in range(3))
    noise = jax.random.uniform(jax.random.fold_in(key, 9), shape)
    return x, y, my, noise


@pytest.mark.parametrize("shape", [(64,), (1000,), (3, 5, 7), (32769,)])
def test_choco_qsgd_fused_equals_unfused_f32(shape):
    """The fused kernel reproduces the unfused
    choco_move -> qsgd_quantize -> add chain: x_new bitwise, y_new to
    one f32 ulp (the final sign*norm*lvl/(s*c) multiply chain rounds
    differently across separately-compiled kernels on XLA:CPU — the
    quantization LEVEL picked is identical, only the last bit of the
    reconstruction can differ)."""
    x, y, my, noise = _fused_inputs(shape, jnp.float32)

    @jax.jit
    def fused(x, y, my, noise):
        return ops.choco_qsgd_move(x, y, my, 0.5, noise, levels=16,
                                   interpret=True)

    @jax.jit
    def unfused(x, y, my, noise):
        x_new, d = ops.choco_move(x, y, my, 0.5, interpret=True)
        q = ops.qsgd_quantize(d, noise, levels=16, interpret=True)
        return x_new, y + q

    xf, yf = fused(x, y, my, noise)
    xu, yu = unfused(x, y, my, noise)
    assert jnp.array_equal(xf, xu)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yu), rtol=3e-7,
                               atol=3e-7)


@pytest.mark.parametrize("shape", [(64,), (1000,), (3, 5, 7), (32769,)])
def test_choco_topk_fused_equals_unfused_f32(shape):
    """Bitwise: the fused TopK kernel masks the SAME materialized diff
    tensor its threshold was selected from, so the kept set cannot drift
    (see choco_fused.choco_topk_2d)."""
    x, y, my, _ = _fused_inputs(shape, jnp.float32)
    k = max(1, int(np.prod(shape)) // 4)

    @jax.jit
    def fused(x, y, my):
        return ops.choco_topk_move(x, y, my, 0.5, k, interpret=True)

    @jax.jit
    def unfused(x, y, my):
        x_new, d = ops.choco_move(x, y, my, 0.5, interpret=True)
        return x_new, y + ops.top_k_compress(d, k, interpret=True)

    xf, yf = fused(x, y, my)
    xu, yu = unfused(x, y, my)
    assert jnp.array_equal(xf, xu)
    assert jnp.array_equal(yf, yu)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_choco_fused_matches_oracle(dtype):
    shape = (300, 70)
    x, y, my, noise = _fused_inputs(shape, dtype)
    d = int(np.prod(shape))
    s = 16.0
    c = 1.0 + min(d / (s * s), d**0.5 / s)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    got = ops.choco_qsgd_move(x, y, my, 0.5, noise, levels=16,
                              interpret=True)
    want = ref.choco_qsgd_ref(x, y, my, 0.5, noise, levels=16, c=c)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), rtol=tol,
                                   atol=tol)
    got = ops.choco_topk_move(x, y, my, 0.5, d // 4, interpret=True)
    want = ref.choco_topk_ref(x, y, my, 0.5, d // 4)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), rtol=tol,
                                   atol=tol)


def test_fused_choco_fewer_buffer_passes():
    """The reason the fused kernel exists: fewer pad round-trips AND
    fewer kernel launches than the unfused composition (counted on the
    un-jitted wrapper bodies, where the counters tick per call)."""
    x, y, my, noise = _fused_inputs((3, 5, 7), jnp.float32)

    with ops.op_stats_delta() as fused:
        ops.eager_impl("choco_qsgd_move")(x, y, my, 0.5, noise, levels=16,
                                          interpret=True)
    with ops.op_stats_delta() as unfused:
        _, d = ops.eager_impl("choco_move")(x, y, my, 0.5, interpret=True)
        ops.eager_impl("qsgd_quantize")(d, noise, levels=16, interpret=True)
    assert fused["pallas_calls"] < unfused["pallas_calls"], (
        fused.as_dict(), unfused.as_dict())
    assert fused["pad_roundtrips"] < unfused["pad_roundtrips"], (
        fused.as_dict(), unfused.as_dict())

    with ops.op_stats_delta() as fused:
        ops.eager_impl("choco_topk_move")(x, y, my, 0.5, k=26,
                                          tmode="interpret", interpret=True)
    with ops.op_stats_delta() as unfused:
        _, d = ops.eager_impl("choco_move")(x, y, my, 0.5, interpret=True)
        ops.eager_impl("top_k_compress")(d, k=26, tmode="interpret",
                                         imask=True)
    assert fused["pallas_calls"] < unfused["pallas_calls"], (
        fused.as_dict(), unfused.as_dict())
    assert fused["pad_roundtrips"] < unfused["pad_roundtrips"], (
        fused.as_dict(), unfused.as_dict())


def test_op_stats_delta_scoping_and_reset_deprecation():
    """Snapshot/delta attribution: nested scopes each see their own
    window, reading an open scope raises, and nothing resets the global
    counters (the old ``reset_op_stats`` raced concurrent scopes and is
    gone)."""
    x, y, my, _noise = _fused_inputs((2, 3), jnp.float32)
    with ops.op_stats_delta() as outer:
        ops.eager_impl("choco_move")(x, y, my, 0.5, interpret=True)
        with pytest.raises(RuntimeError, match="still open"):
            outer.as_dict()
        with ops.op_stats_delta() as inner:
            ops.eager_impl("choco_move")(x, y, my, 0.5, interpret=True)
    # choco_move pads x, y, mixed_y: 3 round-trips, 1 launch per call.
    assert inner.as_dict() == {"pad_roundtrips": 3, "pallas_calls": 1}
    assert outer.pad_roundtrips == 6 and outer.pallas_calls == 2
    snapshot = ops.op_stats()
    snapshot["pallas_calls"] = 0      # a copy: a caller cannot clobber them
    assert ops.op_stats()["pallas_calls"] >= 2
    assert not hasattr(ops, "reset_op_stats")


# ---------------------------------------------------------------------------
# Registry: lazy backend detection + per-op dispatch guards
# ---------------------------------------------------------------------------


def test_backend_detection_is_lazy(monkeypatch):
    """The ISSUE-5 fix: backend choice is read at CALL time, so a backend
    that initializes after `import repro.kernels` still gets Mosaic
    dispatch (the old ops.ON_TPU import-time constant pinned interpret
    mode forever)."""
    from repro.kernels import registry

    try:
        registry.reset_backend_cache()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert registry.on_tpu()
        assert registry.resolve_mode("qsgd_quantize", None) == "mosaic"
        assert registry.resolve_mode("choco_qsgd", None) == "mosaic"
        # ops Mosaic can't lower fall back to plain XLA on TPU
        assert registry.resolve_mode("topk_partials", None) == "fallback"
        # the cache holds until reset
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert registry.on_tpu()
        registry.reset_backend_cache()
        assert not registry.on_tpu()
        assert registry.resolve_mode("qsgd_quantize", None) == "interpret"
        # explicit interpret always wins
        assert registry.resolve_mode("qsgd_quantize", True) == "interpret"
        assert registry.resolve_mode("qsgd_quantize", False) == "mosaic"
        # ...except that an op Mosaic can't lower is never forced into it
        assert registry.resolve_mode("topk_partials", False) == "fallback"
    finally:
        registry.reset_backend_cache()


def test_topk_tpu_fallback_mode_is_bitwise():
    """The plain-XLA threshold fallback (what a TPU host runs for the
    candidate pass) produces the same compressed output bit-for-bit."""
    from repro.kernels.ops import _top_k_compress

    x = jax.random.normal(jax.random.key(2), (5000,))
    a = _top_k_compress(x, k=500, tmode="fallback", imask=True)
    b = _top_k_compress(x, k=500, tmode="interpret", imask=True)
    assert jnp.array_equal(a, b)
    assert jnp.array_equal(a, ref.top_k_ref(x, 500))


def test_on_tpu_constant_is_deprecated():
    from repro.kernels import ops as ops_mod

    with pytest.warns(DeprecationWarning, match="lazy"):
        val = ops_mod.ON_TPU
    assert isinstance(val, bool)


def test_registry_lists_all_ops_with_oracles():
    from repro.kernels import registry

    names = {op.name for op in registry.list_ops()}
    assert {"qsgd_quantize", "gossip_mix", "choco_move", "topk_partials",
            "topk_mask", "choco_qsgd", "choco_topk"} <= names
    with pytest.raises(ValueError, match="unknown kernel op"):
        registry.get_op("nope")


def test_parity_suite_all_ok():
    """The reference-parity harness (what bench_kernels asserts in CI):
    every registered op agrees with its oracle; bitwise ops EXACTLY."""
    from repro.kernels import registry

    records = registry.parity_suite(shapes=[(64,), (1000,), (300, 70)],
                                    dtypes=[jnp.float32, jnp.bfloat16])
    bad = [r for r in records if not r["ok"]]
    assert not bad, bad
    topk_recs = [r for r in records if r["op"] in ("topk_partials",
                                                   "topk_mask")]
    assert topk_recs and all(r["max_err"] == 0.0 for r in topk_recs)


# ---------------------------------------------------------------------------
# jax.disable_jit vs pallas interpret kernels (why eager_impl exists)
# ---------------------------------------------------------------------------

DISABLE_JIT_SCRIPT = r"""
import sys
sys.setrecursionlimit(600)   # bound the blowup: fail fast, not a core dump
import jax, jax.numpy as jnp
from repro.kernels import ops, ref

x = jax.random.normal(jax.random.key(0), (1000,))
want = ref.top_k_ref(x, 100)
try:
    with jax.disable_jit():
        got = ops.top_k_compress(x, 100, interpret=True)
except RecursionError:
    # the pinned jaxlib: pallas interpret mode re-enters itself under
    # disable_jit. This is WHY ops.eager_impl exists and why the
    # no-disable-jit lint rule bans disable_jit in kernels/.
    print("RECURSION_PINNED")
else:
    # a future jax may fix the recursion; then it must also be correct.
    assert jnp.array_equal(got, want)
    print("DISABLE_JIT_OK")

# eager_impl is the supported un-jitted path either way — same bits.
eager = ops.eager_impl("top_k_compress")(x, k=100, tmode="interpret",
                                         imask=True)
assert jnp.array_equal(eager, want)
print("EAGER_IMPL_OK")
"""


@pytest.mark.slow
def test_disable_jit_recursion_pinned_and_eager_impl_escape():
    """Pins the disable_jit/pallas interaction the no-disable-jit lint
    rule (repro.analysis) guards: on the pinned jaxlib interpret-mode
    kernels RECURSE under jax.disable_jit (a newer jax may instead
    succeed — then bitwise-correctly), while ops.eager_impl stays the
    supported un-jitted instrumentation path on every version."""
    import os
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([_sys.executable, "-c", DISABLE_JIT_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert ("RECURSION_PINNED" in out.stdout
            or "DISABLE_JIT_OK" in out.stdout), out.stdout
    assert "EAGER_IMPL_OK" in out.stdout, out.stdout
