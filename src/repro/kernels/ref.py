"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` matches the corresponding kernel bit-for-bit (integer kernels)
or to float tolerance (attention).  Tests sweep shapes/dtypes in interpret
mode against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fxp
from repro.core.activations import (get_qsigmoid, sigmoid_pwl2, sigmoid_pwl4,
                                    sigmoid_rational)
from repro.core.trees import TreeArrays, predict_oblivious

__all__ = ["fxp_qmatmul_ref", "fxp_layer_ref", "fxp_layer_ref_with_stats",
           "fxp_mlp_model_ref", "fxp_svm_model_ref", "fxp_mlp_fleet_ref",
           "fxp_svm_fleet_ref", "pwl_activation_ref", "tree_ensemble_ref",
           "rbf_chain_ref",
           "flash_attention_ref"]


def fxp_qmatmul_ref(a: jax.Array, b: jax.Array, fmt: fxp.FxpFormat,
                    shift: int | None = None) -> jax.Array:
    """Integer-exact oracle: the MCU round-shift-saturate matmul model.

    ``shift`` overrides the requantization amount for mixed-format operands
    (``ma + mb - m_out``, per the artifact's QuantPlan); None keeps the
    single-format semantics (shift by ``fmt.frac_bits``).
    """
    acc = jax.lax.dot_general(a.astype(jnp.int64), b.astype(jnp.int64),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int64)
    return fxp.requantize(acc, fmt.frac_bits if shift is None else shift, fmt)


def fxp_layer_ref(a: jax.Array, b: jax.Array, bias: jax.Array,
                  fmt: fxp.FxpFormat, activation: str = "none",
                  shift: int | None = None) -> jax.Array:
    """Fused-layer oracle: the chained ops, composed.

    ``act(qadd(fxp_qmatmul_ref(a, b), bias))`` — by construction bit-identical
    to the historical three-dispatch path, which is the fused kernel's
    correctness contract (modulo the documented int32-vs-int64 accumulator
    range for extreme inputs).  ``bias`` and the output share ``fmt``;
    ``shift`` carries mixed-format inputs into it (see fxp_qmatmul_ref).
    """
    h = fxp_qmatmul_ref(a, b, fmt, shift)
    h = fxp.qadd(h, bias[None, :], fmt)
    if activation != "none":
        h = get_qsigmoid(activation)(h, fmt)
    return h


def fxp_layer_ref_with_stats(a: jax.Array, b: jax.Array, bias: jax.Array,
                             fmt: fxp.FxpFormat, activation: str = "none",
                             shift: int | None = None):
    """Fused-layer oracle with the matmul stage's overflow/underflow stats
    (the same accounting the chained ref/xla lowerings reported)."""
    h, stats = fxp.qmatmul_with_stats(a, b, fmt, shift)
    h = fxp.qadd(h, bias[None, :], fmt)
    if activation != "none":
        h = get_qsigmoid(activation)(h, fmt)
    return h, stats


def fxp_mlp_model_ref(x: jax.Array, weights, biases, schedule) -> jax.Array:
    """Whole-model MLP oracle: the per-layer fused oracle, composed.

    ``schedule`` is the megakernel's static per-layer plan — one
    ``(shift, out_format, activation)`` triple per layer (see
    :mod:`repro.kernels.fxp_model`).  By construction this is the per-layer
    path bit for bit, which is the megakernel's correctness contract.
    """
    h = x
    for (shift, fmt, activation), w, b in zip(schedule, weights, biases):
        h = fxp_layer_ref(h, w, b, fmt, activation, shift)
    return h


def rbf_chain_ref(qx: jax.Array, sv: jax.Array, chain,
                  dot: jax.Array | None = None) -> jax.Array:
    """The calibrated rbf kernel values (M, S) in ``chain.kernel_fmt``.

    The squared distance ``x2 - 2 dot + sv2`` is summed in int64 and
    wrapped to int32 once (exact whenever it fits int32, and equal mod 2^32
    to the kernels' int32 sums of the same terms), a wrapped negative read
    as the largest int32; ``scale_acc`` carries it into the exponent's
    format and ``qexp`` into the kernel value's.  ``dot`` is the raw
    x·svᵀ accumulator where a kernel computed it (only its value mod 2^32
    counts).
    """
    if dot is None:
        dot = jax.lax.dot_general(qx.astype(jnp.int64), sv.astype(jnp.int64),
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int64)

    def norm(v):
        w = v.astype(jnp.int64)
        return jnp.sum(w * w, -1)

    d2 = (norm(qx)[:, None] - 2 * dot.astype(jnp.int64)
          + norm(sv)[None, :]).astype(jnp.int32)
    d2 = jnp.where(d2 < 0, jnp.int32(np.iinfo(np.int32).max), d2)
    arg = fxp.scale_acc(d2, chain.scale, chain.exp_fmt)
    return fxp.qexp(fxp.qneg(arg, chain.exp_fmt), chain.exp_fmt,
                    chain.kernel_fmt)


def fxp_svm_model_ref(qx: jax.Array, sv: jax.Array, dual: jax.Array,
                      icept: jax.Array, kind: str, fmt: fxp.FxpFormat,
                      out_fmt: fxp.FxpFormat, qgamma: int, qcoef0: int,
                      degree: int, dec_shift: int, chain=None) -> jax.Array:
    """Whole-model kernel-SVM oracle: the chained decision function.

    Mirrors the per-stage lowering exactly — ``fxp_qmatmul_ref`` for
    x·svᵀ, the shared elementwise Qn.m kernel algebra, and the fused-layer
    oracle for the decision stage — so the megakernel's single dispatch has
    a composed-from-parts oracle to be bit-identical to.  ``sv`` is the
    un-transposed (S, F) support-vector matrix; ``qgamma``/``qcoef0`` are
    the quantized integer constants; ``chain`` (a calibrated rbf's
    :class:`repro.kernels.fxp_model.RbfChain`) selects :func:`rbf_chain_ref`.
    """
    if chain is not None:
        k = rbf_chain_ref(qx, sv, chain)
        return fxp_layer_ref(k, dual, icept, out_fmt, "none", dec_shift)
    dot = fxp_qmatmul_ref(qx, sv.T, fmt)
    g = jnp.asarray(qgamma, fmt.dtype)
    if kind == "poly":
        k = fxp.qadd(fxp.qmul(dot, g, fmt),
                     jnp.asarray(qcoef0, fmt.dtype), fmt)
        k = fxp.qpow_int(k, degree, fmt)
    elif kind == "rbf":
        def _qsq_norm(qv):
            wide = qv.astype(fmt.wide_dtype)
            return fxp.rshift_round_saturate(jnp.sum(wide * wide, -1), fmt)

        d2 = fxp.qadd(fxp.qsub(_qsq_norm(qx)[:, None],
                               fxp.qadd(dot, dot, fmt), fmt),
                      _qsq_norm(sv)[None, :], fmt)
        k = fxp.qexp(fxp.qneg(fxp.qmul(d2, g, fmt), fmt), fmt)
    else:
        raise KeyError(f"kind must be 'poly' or 'rbf', got {kind!r}")
    return fxp_layer_ref(k, dual, icept, out_fmt, "none", dec_shift)


def fxp_mlp_fleet_ref(x: jax.Array, weights, biases, schedules) -> jax.Array:
    """Fleet-stacked MLP oracle: the single-model oracle per slot, stacked.

    x: (E, M, K0); weights[i]: (E, K_i, K_{i+1}); biases[i]: (E, K_{i+1});
    ``schedules[e]`` is model e's static layer plan.  Slot e of the output
    IS model e's :func:`fxp_mlp_model_ref` — the fleet kernel's contract
    that stacking never mixes models is checked against exactly this.
    """
    return jnp.stack([
        fxp_mlp_model_ref(x[e], [w[e] for w in weights],
                          [b[e] for b in biases], schedules[e])
        for e in range(x.shape[0])])


def fxp_svm_fleet_ref(qx: jax.Array, sv: jax.Array, dual: jax.Array,
                      icept: jax.Array, kind: str, params) -> jax.Array:
    """Fleet-stacked kernel-SVM oracle (see :func:`fxp_mlp_fleet_ref`);
    ``params[e]`` = (fmt, out_fmt, qgamma, qcoef0, degree, dec_shift,
    chain)."""
    return jnp.stack([
        fxp_svm_model_ref(qx[e], sv[e], dual[e], icept[e], kind, *params[e])
        for e in range(qx.shape[0])])


def pwl_activation_ref(x: jax.Array, variant: str) -> jax.Array:
    x32 = x.astype(jnp.float32)
    if variant == "pwl2":
        y = sigmoid_pwl2(x32)
    elif variant == "pwl4":
        y = sigmoid_pwl4(x32)
    elif variant == "rational":
        y = sigmoid_rational(x32)
    elif variant == "silu_pwl4":
        y = x32 * sigmoid_pwl4(x32)
    else:
        raise KeyError(variant)
    return y.astype(x.dtype)


def tree_ensemble_ref(tree: TreeArrays, x: jax.Array) -> jax.Array:
    return predict_oblivious(tree, x)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """(BH, S, dh) softmax attention, f32 internals."""
    s = q.shape[1]
    scores = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * np.float32(1 / np.sqrt(q.shape[-1]))
    if causal:
        pos = jnp.arange(s)
        scores = jnp.where(pos[:, None] >= pos[None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
