"""Lowering for SVM classifiers: linear / polynomial / RBF kernels.

``svm-linear`` delegates to the shared linear program (same artifact math as
logistic regression).  Kernel machines compute the libsvm decision function
``argmax_c sum_m alpha[m,c] K(x, sv_m) + b[c]``; the float path serves the
f64-trained artifact in f32 (reproducing the paper's poly-SVC precision-drop
finding), the fixed-point path runs the full kernel in Qn.m integer ops.

Backend routing: on ``pallas`` the whole quantized decision function —
x @ sv.T, the poly/rbf elementwise algebra, and the decision stage
(k @ dual + intercept) — is ONE ``kernels/fxp_model`` megakernel dispatch
when the support vectors + duals fit the VMEM budget, recorded as
``extras["kernel_strategy"]``.  Past the budget it falls back to the
chained path (``kernels/fxp_qmatmul`` then the fused ``kernels/fxp_layer``
decision, elementwise kernel math on jnp ops), bit-identical; ``ref``/
``xla`` keep the wide-accumulate oracle spelling throughout.

Quantized tensor paths: the whole feature/kernel domain — ``input``,
``support_vectors``, and every elementwise intermediate up to the kernel
value ``kernel`` — shares ONE scale group (the d2 / qpow algebra adds and
multiplies them against each other, so mixed scales there would need a
requantize per elementwise op); the decision stage then crosses formats:
``dual_coef`` gets its own, and ``out`` (grouped with ``intercept``)
receives the ``m_k + m_dual - m_out`` epilogue shift.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fxp
from repro.quant import Calibration, amax

from ..registry import Lowered, Lowering, register_lowering
from ..target import Target
from .common import (check_pallas_container, elem_bytes, nbytes, q,
                     qx_with_stats, resolve_formats, zero_stats)
from .linear import calibrate_linear, lower_linear


@register_lowering("svm-linear", "svm-poly", "svm-rbf")
class SVMLowering(Lowering):
    def extract_params(self, model: Any) -> Dict[str, Any]:
        if model.kernel == "linear":
            return {"kernel": "linear",
                    "coef": np.asarray(model.coef),
                    "intercept": np.asarray(model.intercept)}
        return {"kernel": str(model.kernel),
                "support_vectors": np.asarray(model.support_vectors),
                "dual_coef": np.asarray(model.dual_coef),
                "intercept": np.asarray(model.intercept),
                "gamma": float(model.gamma),
                "coef0": float(model.coef0),
                "degree": int(model.degree)}

    def calibrate(self, params: Dict[str, Any], x: Any,
                  target: Target) -> Calibration:
        if params["kernel"] == "linear":
            return calibrate_linear(
                np.asarray(params["coef"], np.float32),
                np.asarray(params["intercept"], np.float32),
                np.asarray(x, np.float32))
        return _calibrate_kernel_svm(params, np.asarray(x, np.float32))

    def lower(self, qparams: Dict[str, Any], target: Target,
              plan: Optional[Any] = None) -> Lowered:
        if qparams["kernel"] == "linear":
            return lower_linear(qparams["coef"], qparams["intercept"],
                                target, plan)
        return _lower_kernel_svm(qparams, target, plan)


def _calibrate_kernel_svm(p: Dict[str, Any], x: np.ndarray) -> Calibration:
    """Float replay of the quantized kernel-SVM op sequence.

    Every elementwise intermediate lives in the shared feature-domain format
    (see the module docstring), so its peak folds into the ``kernel`` range.
    """
    sv = np.asarray(p["support_vectors"], np.float32)
    dual = np.asarray(p["dual_coef"], np.float32)
    icept = np.asarray(p["intercept"], np.float32)
    gamma, coef0, degree = p["gamma"], p["coef0"], int(p["degree"])

    dot = x @ sv.T
    # Constants quantized into the feature-domain format, plus 1.0 (qpow's
    # multiplicative identity / the RBF kernel's k <= 1 output).
    kdom = amax(np.float32(gamma), np.float32(coef0), 1.0)
    if p["kernel"] == "poly":
        base = np.float32(gamma) * dot + np.float32(coef0)
        kdom = max(kdom, amax(dot, base))
        # qpow_int's square-and-multiply intermediates all live in-format.
        k, b, d = np.ones_like(base), base, degree
        while d:
            if d & 1:
                k = k * b
                kdom = max(kdom, amax(k))
            b = b * b
            d >>= 1
            if d:
                kdom = max(kdom, amax(b))
    else:  # rbf
        x2 = np.sum(x * x, axis=-1)
        sv2 = np.sum(sv * sv, axis=-1)
        d2 = x2[:, None] - 2.0 * dot + sv2[None, :]
        arg = -np.float32(gamma) * d2
        k = np.exp(arg)
        kdom = max(kdom, amax(x2, sv2, dot, d2, arg, k))

    acc = k @ dual
    out = acc + icept
    matmuls = [("input", "support_vectors", "kernel"),
               ("kernel", "dual_coef", "out")]
    acc_ranges = {"kernel": amax(dot), "out": amax(acc)}
    if p["kernel"] == "rbf":
        # _qsq_norm accumulates sum(q^2) with the same shift epilogue.
        matmuls += [("input", "input", "kernel"),
                    ("support_vectors", "support_vectors", "kernel")]
        acc_ranges["kernel"] = amax(dot, x2, sv2)
    return Calibration(
        ranges={"input": amax(x), "support_vectors": amax(sv),
                "kernel": kdom, "dual_coef": amax(dual),
                "intercept": amax(icept), "out": amax(out, icept)},
        groups=(("input", "support_vectors", "kernel"),
                ("intercept", "out")),
        matmuls=tuple(matmuls),
        acc_ranges=acc_ranges,
    )


def _lower_kernel_svm(p: Dict[str, Any], target: Target,
                      plan: Optional[Any] = None) -> Lowered:
    F = resolve_formats(target, plan)
    kernel = p["kernel"]
    sv = np.asarray(p["support_vectors"])
    dual = np.asarray(p["dual_coef"])
    icept = np.asarray(p["intercept"])
    gamma, coef0, degree = p["gamma"], p["coef0"], p["degree"]
    extras: Dict[str, Any] = {}

    if F is None:
        svj = jnp.asarray(sv, jnp.float32)  # f32 serve of the f64 artifact
        dj = jnp.asarray(dual, jnp.float32)
        bj = jnp.asarray(icept, jnp.float32)

        if kernel == "poly":
            def predict(x):
                x = jnp.asarray(x, jnp.float32)
                k = (np.float32(gamma) * (x @ svj.T) + np.float32(coef0)) ** degree
                return jnp.argmax(k @ dj + bj, -1).astype(jnp.int32), zero_stats()
        else:  # rbf
            def predict(x):
                x = jnp.asarray(x, jnp.float32)
                d2 = (jnp.sum(x * x, -1, keepdims=True) - 2 * x @ svj.T
                      + jnp.sum(svj * svj, -1)[None, :])
                k = jnp.exp(-np.float32(gamma) * d2)
                return jnp.argmax(k @ dj + bj, -1).astype(jnp.int32), zero_stats()

        flash = nbytes(sv.astype(np.float32), dual.astype(np.float32),
                       icept.astype(np.float32))
        sram = (sv.shape[0] + dual.shape[1]) * elem_bytes(None)
    else:
        # One feature/kernel-domain format (grouped with the input by the
        # planner), distinct dual/out formats across the decision matmul.
        fmt = F("kernel")
        out_fmt = F("out")
        qsv = q(sv, F("support_vectors"))
        qd = q(dual, F("dual_coef"))
        qb = q(icept, F("intercept"))  # grouped with 'out'
        qgamma = q(np.float32(gamma), fmt)
        qcoef0 = q(np.float32(coef0), fmt)
        dec_shift = (fmt.frac_bits + F("dual_coef").frac_bits
                     - out_fmt.frac_bits)

        if target.backend == "pallas":
            from repro.kernels import fxp_model, ops

            check_pallas_container(target, fmt)
            extras["kernel_strategy"] = "per-layer"

            def matmul(a, b):
                return ops.fxp_qmatmul(a, b, fmt), zero_stats()

            def decision(k):
                # k @ dual + intercept, fused into one kernel dispatch.
                return ops.fxp_layer(k, qd, qb, out_fmt, activation="none",
                                     shift=dec_shift), zero_stats()
        else:
            from repro.kernels import ref as ref_ops

            def matmul(a, b):
                return fxp.qmatmul_with_stats(a, b, fmt)

            def decision(k):
                return ref_ops.fxp_layer_ref_with_stats(
                    k, qd, qb, out_fmt, activation="none", shift=dec_shift)

        if kernel == "poly":
            def predict(x):
                qx, s0 = qx_with_stats(jnp.asarray(x, jnp.float32), fmt)
                dot, s1 = matmul(qx, qsv.T)
                k = fxp.qadd(fxp.qmul(dot, qgamma, fmt), qcoef0, fmt)
                k = fxp.qpow_int(k, degree, fmt)
                out, s2 = decision(k)
                return jnp.argmax(out, -1).astype(jnp.int32), s0.merge(s1).merge(s2)
        else:  # rbf
            def _qsq_norm(qv):
                # sum_k q_k^2 in wide precision, one rounded shift at the end
                wide = qv.astype(fmt.wide_dtype)
                acc = jnp.sum(wide * wide, axis=-1)
                return fxp.rshift_round_saturate(acc, fmt)

            def predict(x):
                qx, s0 = qx_with_stats(jnp.asarray(x, jnp.float32), fmt)
                # d2 = |x|^2 - 2 x.sv + |sv|^2, all Qn.m
                x2 = _qsq_norm(qx)
                dot, s1 = matmul(qx, qsv.T)
                sv2 = _qsq_norm(qsv)
                d2 = fxp.qadd(fxp.qsub(x2[:, None], fxp.qadd(dot, dot, fmt), fmt),
                              sv2[None, :], fmt)
                arg = fxp.qneg(fxp.qmul(d2, qgamma, fmt), fmt)
                k = fxp.qexp(arg, fmt)
                out, s2 = decision(k)
                return jnp.argmax(out, -1).astype(jnp.int32), s0.merge(s1).merge(s2)

        if target.backend == "pallas" and fxp_model.svm_fits_vmem(
                sv.shape[0], sv.shape[1], dual.shape[1], fmt.total_bits):
            # Kernel evaluation + vote collapsed to ONE dispatch: the whole
            # decision function (x·svᵀ, the poly/rbf algebra, the fused
            # decision stage) in a single pallas_call; the chained per-stage
            # path above remains the VMEM-overflow fallback, bit-identical.
            extras["kernel_strategy"] = "megakernel"
            qgamma_i = int(np.asarray(qgamma))
            qcoef0_i = int(np.asarray(qcoef0))

            def predict(x):  # noqa: F811 — the megakernel override
                qx, s0 = qx_with_stats(jnp.asarray(x, jnp.float32), fmt)
                out = ops.fxp_svm_model(qx, qsv, qd, qb, kernel, fmt,
                                        out_fmt, qgamma_i, qcoef0_i,
                                        int(degree), dec_shift)
                return jnp.argmax(out, -1).astype(jnp.int32), s0

        flash = nbytes(np.asarray(qsv), np.asarray(qd), np.asarray(qb))
        sram = (sv.shape[0] + dual.shape[1]) * elem_bytes(fmt)
        # The C emitter regenerates the same decision function from the
        # quantized tensors and constants the predict paths close over.
        extras["emit_spec"] = {
            "family": "svm",
            "kernel": kernel,
            "fmt": fmt,
            "out_fmt": out_fmt,
            "sv": np.asarray(qsv),
            "dual": np.asarray(qd),
            "b": np.asarray(qb),
            "qgamma": int(np.asarray(qgamma)),
            "qcoef0": int(np.asarray(qcoef0)),
            "degree": int(degree),
            "dec_shift": dec_shift,
        }
    return Lowered(predict, flash, sram, extras=extras)
