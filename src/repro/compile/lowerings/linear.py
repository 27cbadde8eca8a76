"""Lowering for linear decision functions: logistic regression (and, via
delegation from the SVM lowering, linear SVMs — identical artifact math:
``argmax(x @ W + b)``).

Backend routing for fixed-point targets: the decision function is one fused
layer op (matmul + bias in a single dispatch, activation ``none``):
``ref``/``xla`` via the wide-accumulate ``kernels/ref.fxp_layer_ref`` oracle,
``pallas`` via the ``kernels/fxp_layer`` kernel (interpret mode off-TPU).
The pallas path reports quantization stats for the *input* stage only —
kernel-internal saturation accounting stays on the reference backend.

Quantized tensor paths (fixed targets resolve them all to the one global
format; calibrated targets to per-tensor QuantPlan entries):

* ``input``     — the feature vector, quantized at call time;
* ``coef``      — the weight matrix;
* ``out``       — the logits; ``intercept`` is carried at the same scale
  (it is added to the requantized accumulator), so the two share a group.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.quant import Calibration, amax

from ..registry import Lowered, Lowering, register_lowering
from ..target import Target
from .common import (check_pallas_container, elem_bytes, nbytes, q,
                     qx_with_stats, resolve_formats, zero_stats)


def calibrate_linear(coef: np.ndarray, intercept: np.ndarray,
                     x: np.ndarray) -> Calibration:
    """Float replay of ``argmax(x @ coef + intercept)`` collecting ranges."""
    acc = x @ coef
    logits = acc + intercept
    return Calibration(
        ranges={"input": amax(x), "coef": amax(coef),
                "intercept": amax(intercept), "out": amax(logits, intercept)},
        groups=(("intercept", "out"),),
        matmuls=(("input", "coef", "out"),),
        acc_ranges={"out": amax(acc)},
    )


def lower_linear(coef: np.ndarray, intercept: np.ndarray, target: Target,
                 plan: Optional[Any] = None) -> Lowered:
    """Build the Lowered program for ``argmax(x @ coef + intercept)``."""
    F = resolve_formats(target, plan)
    extras: Dict[str, Any] = {}
    if F is None:
        w = jnp.asarray(coef, jnp.float32)
        b = jnp.asarray(intercept, jnp.float32)

        def predict(x):
            x = jnp.asarray(x, jnp.float32)
            return jnp.argmax(x @ w + b, -1).astype(jnp.int32), zero_stats()

        flash = nbytes(np.asarray(coef, np.float32),
                       np.asarray(intercept, np.float32))
        sram = int(np.asarray(coef).shape[1]) * elem_bytes(None)
    else:
        in_fmt, coef_fmt, out_fmt = F("input"), F("coef"), F("out")
        qw = q(coef, coef_fmt)
        qb = q(intercept, F("intercept"))  # grouped with 'out' by the planner
        shift = in_fmt.frac_bits + coef_fmt.frac_bits - out_fmt.frac_bits

        if target.backend == "pallas":
            from repro.kernels import ops

            check_pallas_container(target, in_fmt)

            def predict(x):
                qx, stats = qx_with_stats(jnp.asarray(x, jnp.float32), in_fmt)
                logits = ops.fxp_layer(qx, qw, qb, out_fmt,
                                       activation="none", shift=shift)
                return jnp.argmax(logits, -1).astype(jnp.int32), stats
        else:
            from repro.kernels import ref as ref_ops

            def predict(x):
                qx, s1 = qx_with_stats(jnp.asarray(x, jnp.float32), in_fmt)
                logits, s2 = ref_ops.fxp_layer_ref_with_stats(
                    qx, qw, qb, out_fmt, activation="none", shift=shift)
                return jnp.argmax(logits, -1).astype(jnp.int32), s1.merge(s2)

        flash = nbytes(np.asarray(qw), np.asarray(qb))
        sram = int(np.asarray(coef).shape[1]) * elem_bytes(in_fmt)
        # Everything the C emitter (repro.emit) needs to regenerate this
        # exact program: the already-quantized tensors and the shift the
        # predict above closes over — one source of truth for both backends.
        extras["emit_spec"] = {
            "family": "linear",
            "in_fmt": in_fmt,
            "out_fmt": out_fmt,
            "w": np.asarray(qw),
            "b": np.asarray(qb),
            "shift": shift,
        }
    return Lowered(predict, flash, sram, extras=extras)


@register_lowering("logistic")
class LogisticLowering(Lowering):
    def extract_params(self, model: Any) -> Dict[str, Any]:
        return {"coef": np.asarray(model.coef),
                "intercept": np.asarray(model.intercept)}

    def calibrate(self, params: Dict[str, Any], x: Any,
                  target: Target) -> Calibration:
        return calibrate_linear(np.asarray(params["coef"], np.float32),
                                np.asarray(params["intercept"], np.float32),
                                np.asarray(x, np.float32))

    def lower(self, qparams: Dict[str, Any], target: Target,
              plan: Optional[Any] = None) -> Lowered:
        return lower_linear(qparams["coef"], qparams["intercept"], target,
                            plan)
