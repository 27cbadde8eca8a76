"""Lowering for sigmoid-MLP classifiers (paper C3: sigmoid replacements).

Backend routing:

* float targets — plain XLA matmuls; the ``pallas`` backend additionally
  routes non-exact sigmoids through the fused ``kernels/pwl_activation``
  VPU kernel.
* fixed-point targets — every layer is one *fused* op,
  ``act(qadd(qmatmul(h, W), b))``: ``ref``/``xla`` via the wide-accumulate
  ``kernels/ref.fxp_layer_ref_with_stats`` oracle.  On ``pallas`` the
  *whole forward pass* is one ``kernels/fxp_model`` megakernel dispatch
  when the packed weights fit the VMEM budget (always, for paper-scale
  models): all layers' weights resident, inter-layer activations never
  leaving VMEM, the per-layer shifts frozen into a static schedule.
  Models past the budget fall back to one ``kernels/fxp_layer`` dispatch
  per layer (int32 accumulator resident in VMEM, bias + shift +
  saturation + PWL epilogue on the VPU).  Activations stay in the Qn.m
  integer domain everywhere, and all routes are bit-identical; the chosen
  route is recorded as ``extras["kernel_strategy"]``.

Quantized tensor paths (calibrated targets give each its own Qn.m format;
fixed targets resolve all of them to the global one):

* ``input``            — the feature vector, quantized at call time;
* ``layers/{i}/w``     — layer weights;
* ``layers/{i}/out``   — the layer's pre/post-activation value; the bias
  (``layers/{i}/b``) is added at this scale, so the two share a group.
  Layer ``i+1`` consumes ``layers/{i}/out`` directly — activations never
  requantize between layers; each layer's epilogue shift
  (``m_in + m_w - m_out``) does the rescaling inside the fused op.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.activations import get_sigmoid
from repro.quant import Calibration, activation_range, amax

from ..registry import Lowered, Lowering, register_lowering
from ..target import Target
from .common import (check_pallas_container, elem_bytes, nbytes, q,
                     qx_with_stats, resolve_formats, zero_stats)


@register_lowering("mlp")
class MLPLowering(Lowering):
    def extract_params(self, model: Any) -> Dict[str, Any]:
        return {"weights": [np.asarray(w) for w in model.weights],
                "biases": [np.asarray(b) for b in model.biases]}

    def calibrate(self, params: Dict[str, Any], x: Any,
                  target: Target) -> Calibration:
        weights = [np.asarray(w, np.float32) for w in params["weights"]]
        biases = [np.asarray(b, np.float32) for b in params["biases"]]
        sig = get_sigmoid(target.sigmoid)
        h = np.asarray(x, np.float32)
        ranges = {"input": amax(h)}
        groups, matmuls, acc_ranges = [], [], {}
        prev = "input"
        for i, (w, b) in enumerate(zip(weights, biases)):
            wp, bp, op = f"layers/{i}/w", f"layers/{i}/b", f"layers/{i}/out"
            acc = h @ w
            h = acc + b
            last = i == len(weights) - 1
            ranges[wp] = amax(w)
            ranges[bp] = amax(b)
            # The out format also hosts the sigmoid's in-format constants.
            ranges[op] = activation_range(target.sigmoid, amax(h), last)
            groups.append((bp, op))
            matmuls.append((prev, wp, op))
            acc_ranges[op] = amax(acc)
            if not last:
                h = np.asarray(sig(jnp.asarray(h)), np.float32)
            prev = op
        return Calibration(ranges=ranges, groups=tuple(groups),
                           matmuls=tuple(matmuls), acc_ranges=acc_ranges)

    def lower(self, qparams: Dict[str, Any], target: Target,
              plan: Optional[Any] = None) -> Lowered:
        F = resolve_formats(target, plan)
        weights = qparams["weights"]
        biases = qparams["biases"]
        widths = [int(weights[0].shape[0])] + [int(w.shape[1]) for w in weights]
        extras: Dict[str, Any] = {}

        if F is None:
            ws = [jnp.asarray(w, jnp.float32) for w in weights]
            bs = [jnp.asarray(b, jnp.float32) for b in biases]
            if target.backend == "pallas" and target.sigmoid in (
                    "pwl2", "pwl4", "rational"):
                from repro.kernels import ops
                variant = target.sigmoid
                sig = lambda h: ops.pwl_activation(h, variant)
            else:
                sig = get_sigmoid(target.sigmoid)

            def predict(x):
                h = jnp.asarray(x, jnp.float32)
                for i, (w, b) in enumerate(zip(ws, bs)):
                    h = h @ w + b
                    if i < len(ws) - 1:
                        h = sig(h)
                return jnp.argmax(h, -1).astype(jnp.int32), zero_stats()

            flash = nbytes(*[np.asarray(w, np.float32) for w in weights],
                           *[np.asarray(b, np.float32) for b in biases])
            sram = max(widths) * elem_bytes(None)
        else:
            in_fmt = F("input")
            w_fmts = [F(f"layers/{i}/w") for i in range(len(weights))]
            out_fmts = [F(f"layers/{i}/out") for i in range(len(weights))]
            qws = [q(w, f) for w, f in zip(weights, w_fmts)]
            # biases ride at the layer-out scale (grouped by the planner)
            qbs = [q(b, F(f"layers/{i}/b"))
                   for i, b in enumerate(biases)]
            in_fracs = [in_fmt.frac_bits] + [f.frac_bits for f in out_fmts[:-1]]
            shifts = [fi + fw.frac_bits - fo.frac_bits
                      for fi, fw, fo in zip(in_fracs, w_fmts, out_fmts)]
            # Hidden layers fuse the sigmoid into the layer op; the output
            # layer emits raw logits ("none").
            acts = [target.sigmoid] * (len(qws) - 1) + ["none"]

            if target.backend == "pallas":
                from repro.kernels import fxp_model, ops

                check_pallas_container(target, in_fmt)
                # The whole forward as ONE dispatch when the packed weights
                # fit the VMEM budget (always, for paper-scale models);
                # otherwise the PR-3 per-layer fused path — bit-identical
                # either way, the routing is purely a dispatch-count/VMEM
                # decision and is recorded on the artifact's cache key.
                schedule = tuple(zip(shifts, out_fmts, acts))
                if fxp_model.mlp_fits_vmem(widths, in_fmt.total_bits):
                    strategy = "megakernel"

                    def predict(x):
                        h, stats = qx_with_stats(jnp.asarray(x, jnp.float32),
                                                 in_fmt)
                        out = ops.fxp_mlp_model(h, tuple(qws), tuple(qbs),
                                                schedule)
                        return jnp.argmax(out, -1).astype(jnp.int32), stats
                else:
                    strategy = "per-layer"

                    def predict(x):
                        h, stats = qx_with_stats(jnp.asarray(x, jnp.float32),
                                                 in_fmt)
                        for w, b, act, fo, sh in zip(qws, qbs, acts, out_fmts,
                                                     shifts):
                            h = ops.fxp_layer(h, w, b, fo, activation=act,
                                              shift=sh)
                        return jnp.argmax(h, -1).astype(jnp.int32), stats

                extras = {"kernel_strategy": strategy}
            else:
                from repro.kernels import ref as ref_ops

                def predict(x):
                    h, stats = qx_with_stats(jnp.asarray(x, jnp.float32),
                                             in_fmt)
                    for w, b, act, fo, sh in zip(qws, qbs, acts, out_fmts,
                                                 shifts):
                        h, s = ref_ops.fxp_layer_ref_with_stats(
                            h, w, b, fo, activation=act, shift=sh)
                        stats = stats.merge(s)
                    return jnp.argmax(h, -1).astype(jnp.int32), stats

            flash = nbytes(*[np.asarray(w) for w in qws],
                           *[np.asarray(b) for b in qbs])
            # One reused activation buffer (paper §III-D): the widest layer.
            sram = max(widths) * elem_bytes(in_fmt)
            # The C emitter regenerates this program from the same quantized
            # tensors and per-layer shift/activation schedule.
            extras["emit_spec"] = {
                "family": "mlp",
                "in_fmt": in_fmt,
                "out_fmts": out_fmts,
                "ws": [np.asarray(w) for w in qws],
                "bs": [np.asarray(b) for b in qbs],
                "shifts": shifts,
                "acts": acts,
            }
        return Lowered(predict, flash, sram, extras=extras)
