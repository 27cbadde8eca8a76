"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Shape/dtype sweeps as required: parametrized grids + hypothesis randoms.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.core.fixedpoint import FXP8, FXP16, FXP32
from repro.kernels import ops
from repro.kernels import ref as R
from repro.models.decision_tree import train_decision_tree


# ---------------------------------------------------------------------------
# fxp_qmatmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", [FXP32, FXP16, FXP8], ids=str)
@pytest.mark.parametrize("shape", [(8, 16, 8), (100, 300, 70), (128, 256, 128),
                                   (1, 1, 1), (17, 129, 33)])
def test_fxp_qmatmul_matches_ref(fmt, shape):
    m, k, n = shape
    rng = np.random.RandomState(hash(shape) % 2**31)
    lim = min(2000, fmt.qmax // 2)
    a = rng.randint(-lim, lim, (m, k)).astype(np.dtype(fmt.dtype))
    b = rng.randint(-lim, lim, (k, n)).astype(np.dtype(fmt.dtype))
    got = np.asarray(ops.fxp_qmatmul(jnp.asarray(a), jnp.asarray(b), fmt))
    want = np.asarray(R.fxp_qmatmul_ref(jnp.asarray(a), jnp.asarray(b), fmt))
    np.testing.assert_array_equal(got, want)


def test_fxp_qmatmul_saturates():
    # FXP8: int8 inputs can never wrap the int32 MXU accumulator (K < 133k),
    # so output saturation is exact.  (FXP16 at extreme magnitudes can wrap
    # the accumulator — the documented int32-accumulate contract; the 'xla'
    # impl keeps int64 semantics for that regime.)
    fmt = FXP8
    a = np.full((4, 256), fmt.qmax, np.int8)
    b = np.full((256, 4), fmt.qmax, np.int8)
    got = np.asarray(ops.fxp_qmatmul(jnp.asarray(a), jnp.asarray(b), fmt))
    assert np.all(got == fmt.qmax)
    want = np.asarray(R.fxp_qmatmul_ref(jnp.asarray(a), jnp.asarray(b), fmt))
    np.testing.assert_array_equal(got, want)


def test_fxp_qmatmul_xla_impl_full_range():
    # the reference path keeps int64 accumulation for full-range int16 sums
    fmt = FXP16
    a = jnp.asarray(np.full((4, 64), 8000, np.int16))
    b = jnp.asarray(np.full((64, 4), 8000, np.int16))
    got = np.asarray(ops.fxp_qmatmul(a, b, fmt, impl="xla"))
    assert np.all(got == fmt.qmax)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 64), k=st.integers(1, 128), n=st.integers(1, 64),
       seed=st.integers(0, 2**31 - 1))
def test_property_fxp_qmatmul(m, k, n, seed):
    fmt = FXP16
    rng = np.random.RandomState(seed)
    a = rng.randint(-3000, 3000, (m, k)).astype(np.int16)
    b = rng.randint(-3000, 3000, (k, n)).astype(np.int16)
    got = np.asarray(ops.fxp_qmatmul(jnp.asarray(a), jnp.asarray(b), fmt))
    want = np.asarray(R.fxp_qmatmul_ref(jnp.asarray(a), jnp.asarray(b), fmt))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# mxu_dot — every integer dot in the MXU's int8 x int8 -> int32 form
# ---------------------------------------------------------------------------
_DOT_CASES = {
    "matmul": ((9, 40), (40, 7), (((1,), (0,)), ((), ()))),
    "x_svT": ((9, 40), (7, 40), (((1,), (1,)), ((), ()))),
    "batched": ((3, 9, 40), (3, 40, 7), (((2,), (1,)), ((0,), (0,)))),
}


@pytest.mark.parametrize("case", sorted(_DOT_CASES))
@pytest.mark.parametrize("dtype", [np.int8, np.int16])
def test_mxu_dot_equals_wrapping_int32_dot(case, dtype):
    """int16 limbs recombine to the exact int32 dot modulo 2^32 — full
    range, extremes included, so sums that wrap int32 wrap identically."""
    from repro.kernels.mosaic import mxu_dot

    import zlib

    a_shape, b_shape, dims = _DOT_CASES[case]
    info = np.iinfo(dtype)
    rng = np.random.RandomState(zlib.crc32(case.encode()))
    a = rng.randint(info.min, info.max + 1, a_shape).astype(dtype)
    b = rng.randint(info.min, info.max + 1, b_shape).astype(dtype)
    a.flat[:4] = (info.min, info.max, info.min, -1)
    b.flat[:4] = (info.min, info.min, info.max, info.max)
    got = np.asarray(mxu_dot(jnp.asarray(a), jnp.asarray(b), dims))
    want = np.asarray(jax.lax.dot_general(
        jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32), dims,
        preferred_element_type=jnp.int32))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# fxp_layer — the fused hot-path kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", [FXP32, FXP16, FXP8], ids=str)
@pytest.mark.parametrize("activation", ["none", "pwl2", "pwl4", "rational",
                                        "exact"])
@pytest.mark.parametrize("shape", [(1, 12, 16), (8, 16, 3), (37, 129, 65),
                                   (64, 256, 32)])
def test_fxp_layer_matches_ref(fmt, activation, shape):
    import zlib

    m, k, n = shape
    # crc32, not hash(): str hashes are salted per process, and the parity
    # contract needs reproducible inputs.
    rng = np.random.RandomState(zlib.crc32(repr((shape, activation)).encode()))
    lim = min(2000, fmt.qmax // 2)
    a = rng.randint(-lim, lim, (m, k)).astype(np.dtype(fmt.dtype))
    w = rng.randint(-lim, lim, (k, n)).astype(np.dtype(fmt.dtype))
    b = rng.randint(-lim, lim, (n,)).astype(np.dtype(fmt.dtype))
    got = np.asarray(ops.fxp_layer(jnp.asarray(a), jnp.asarray(w),
                                   jnp.asarray(b), fmt, activation))
    want = np.asarray(R.fxp_layer_ref(jnp.asarray(a), jnp.asarray(w),
                                      jnp.asarray(b), fmt, activation))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fmt", [FXP32, FXP16, FXP8], ids=str)
def test_fxp_layer_equals_chained_ops(fmt):
    """The fused kernel's contract: bit-identical to the historical
    three-dispatch chain (qmatmul -> qadd -> qsigmoid) in every format."""
    from repro.core import fixedpoint as fxp
    from repro.core.activations import get_qsigmoid

    rng = np.random.RandomState(fmt.total_bits)
    lim = min(1500, fmt.qmax // 2)
    a = jnp.asarray(rng.randint(-lim, lim, (9, 40)).astype(np.dtype(fmt.dtype)))
    w = jnp.asarray(rng.randint(-lim, lim, (40, 7)).astype(np.dtype(fmt.dtype)))
    b = jnp.asarray(rng.randint(-lim, lim, (7,)).astype(np.dtype(fmt.dtype)))
    for activation in ("none", "pwl4", "exact"):
        chained = fxp.qadd(ops.fxp_qmatmul(a, w, fmt), b[None, :], fmt)
        if activation != "none":
            chained = get_qsigmoid(activation)(chained, fmt)
        fused = ops.fxp_layer(a, w, b, fmt, activation)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(chained))
        # and the ref oracle agrees with the chained *ref* ops identically
        ref_fused = R.fxp_layer_ref(a, w, b, fmt, activation)
        ref_chained = fxp.qadd(R.fxp_qmatmul_ref(a, w, fmt), b[None, :], fmt)
        if activation != "none":
            ref_chained = get_qsigmoid(activation)(ref_chained, fmt)
        np.testing.assert_array_equal(np.asarray(ref_fused),
                                      np.asarray(ref_chained))


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 48), k=st.integers(1, 96), n=st.integers(1, 48),
       seed=st.integers(0, 2**31 - 1))
def test_property_fxp_layer_fused_vs_chained(m, k, n, seed):
    from repro.core import fixedpoint as fxp
    from repro.core.activations import get_qsigmoid

    fmt = FXP16
    rng = np.random.RandomState(seed)
    a = jnp.asarray(rng.randint(-3000, 3000, (m, k)).astype(np.int16))
    w = jnp.asarray(rng.randint(-3000, 3000, (k, n)).astype(np.int16))
    b = jnp.asarray(rng.randint(-3000, 3000, (n,)).astype(np.int16))
    fused = ops.fxp_layer(a, w, b, fmt, "pwl4")
    chained = get_qsigmoid("pwl4")(
        fxp.qadd(ops.fxp_qmatmul(a, w, fmt), b[None, :], fmt), fmt)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(chained))
    np.testing.assert_array_equal(
        np.asarray(fused),
        np.asarray(R.fxp_layer_ref(a, w, b, fmt, "pwl4")))


def test_fxp_layer_dispatch_count():
    """A fused L-layer forward issues L kernel dispatches (the chained form
    issued one *matmul* dispatch plus two elementwise stages per layer)."""
    fmt = FXP16
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randint(-500, 500, (8, 16)).astype(np.int16))
    layers = [(jnp.asarray(rng.randint(-500, 500, (16, 16)).astype(np.int16)),
               jnp.asarray(rng.randint(-500, 500, (16,)).astype(np.int16)))
              for _ in range(3)]
    with ops.count_dispatches() as c:
        out = h
        for w, b in layers:
            out = ops.fxp_layer(out, w, b, fmt, "pwl4")
    assert c.count == 3


# ---------------------------------------------------------------------------
# pwl_activation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("variant", ["pwl2", "pwl4", "rational", "silu_pwl4"])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
@pytest.mark.parametrize("shape", [(4, 8), (257,), (3, 5, 7), (1024, 16)])
def test_pwl_activation_matches_ref(variant, dtype, shape):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 6).astype(dtype)
    got = np.asarray(ops.pwl_activation(x, variant), np.float32)
    want = np.asarray(R.pwl_activation_ref(x, variant), np.float32)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# tree_ensemble
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [2, 5, 8])
@pytest.mark.parametrize("batch", [1, 100, 512])
def test_tree_ensemble_matches_ref(depth, batch):
    rng = np.random.RandomState(depth * 100 + batch)
    xt = rng.randn(800, 10).astype(np.float32)
    yt = ((xt[:, 0] > 0).astype(np.int32) + (xt[:, 3] > 0.5).astype(np.int32))
    model = train_decision_tree(xt, yt, 3, max_depth=depth)
    xq = jnp.asarray(rng.randn(batch, 10).astype(np.float32) * 2)
    got = np.asarray(ops.tree_predict(model.tree, xq))
    want = np.asarray(R.tree_ensemble_ref(model.tree, xq))
    np.testing.assert_array_equal(got, want)
    # and equals the iterative (MCU) layout
    from repro.core.trees import predict_iterative
    np.testing.assert_array_equal(got, np.asarray(predict_iterative(model.tree, xq)))


@pytest.mark.parametrize("batch,block", [(1, 16), (7, 16), (37, 16),
                                         (100, 64), (257, 256)])
def test_tree_ensemble_ragged_batch(batch, block):
    """Regression: the kernel wrapper pads ragged B internally instead of
    hard-asserting ``B % block_batch == 0``."""
    from repro.kernels.tree_ensemble import pack_tree, tree_ensemble_pallas

    rng = np.random.RandomState(batch)
    xt = rng.randn(500, 8).astype(np.float32)
    yt = (xt[:, 0] > 0).astype(np.int32) + (xt[:, 2] > 0.3).astype(np.int32)
    model = train_decision_tree(xt, yt, 3, max_depth=6)
    xq = jnp.asarray(rng.randn(batch, 8).astype(np.float32))
    packed = tuple(jnp.asarray(t) for t in pack_tree(model.tree))
    got = np.asarray(tree_ensemble_pallas(xq, *packed, block_batch=block,
                                          interpret=True))
    np.testing.assert_array_equal(got, np.asarray(R.tree_ensemble_ref(model.tree, xq)))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,bq,bk", [(128, 64, 64), (256, 128, 64), (64, 64, 64)])
def test_flash_attention_matches_ref(causal, s, bq, bk):
    rng = np.random.RandomState(s + causal)
    q = jnp.asarray(rng.randn(2, s, 32).astype(np.float32))
    k = jnp.asarray(rng.randn(2, s, 32).astype(np.float32))
    v = jnp.asarray(rng.randn(2, s, 32).astype(np.float32))
    got = np.asarray(ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk))
    want = np.asarray(R.flash_attention_ref(q, k, v, causal=causal))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 128, 64).astype(np.float32)).astype(jnp.bfloat16)
    k = jnp.asarray(rng.randn(2, 128, 64).astype(np.float32)).astype(jnp.bfloat16)
    v = jnp.asarray(rng.randn(2, 128, 64).astype(np.float32)).astype(jnp.bfloat16)
    got = np.asarray(ops.flash_attention(q, k, v, bq=64, bk=64), np.float32)
    want = np.asarray(R.flash_attention_ref(q, k, v), np.float32)
    np.testing.assert_allclose(got, want, atol=3e-2)
