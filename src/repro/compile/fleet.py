"""Fleet stacking: many compatible artifacts, ONE stacked Pallas dispatch.

The paper's deployment model is a *fleet* of KB-scale classifiers; served
behind a router, each endpoint's per-dispatch fixed overhead (host batch
assembly, dispatch launch, padding) dwarfs its actual compute.  PRs 3/7
collapsed a *single* model to one dispatch — this module collapses *many
models*: artifacts whose programs are shape-compatible are stacked along a
leading model axis and executed by the fleet megakernels
(:func:`repro.kernels.ops.fxp_mlp_fleet` / ``fxp_svm_fleet``), with each
model's :data:`LayerSchedule` threaded as a static argument so slot ``e``
of the output is bit-identical to member ``e``'s own ``predict``.

Compatibility is *structural*, not behavioral: two members may carry
different weights, different Qm.n splits, even different activation
schedules — the kernel branches per model — but they must agree on the
things that shape the stacked program: model family, layer widths, and the
integer container width.  :func:`fleet_signature` reduces an artifact to
exactly that hashable essence (or ``None`` when the artifact cannot ride a
stack at all); equal signatures == stackable.

A ``logistic`` artifact is a 1-layer MLP to the stacked program — its
single ``fxp_layer`` rides the MLP stack as the schedule
``((shift, out_fmt, "none"),)`` — so logistic and genuinely-1-layer MLP
endpoints of equal shape coalesce into one fleet.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import fixedpoint as fxp
from repro.kernels import fxp_model, ops

__all__ = ["FleetStack", "fleet_signature", "stack_fleet"]

# Hashable structural essence of an artifact for stacking purposes.
FleetSignature = Tuple


def _mlp_spec(artifact) -> Optional[dict]:
    """The artifact's emit spec viewed as an MLP stack member (linear
    families are normalized to a 1-layer schedule), or None."""
    spec = artifact.extras.get("emit_spec")
    if not spec:
        return None
    if spec["family"] == "mlp":
        return spec
    if spec["family"] == "linear":
        return {"family": "mlp", "in_fmt": spec["in_fmt"],
                "out_fmts": (spec["out_fmt"],), "ws": [spec["w"]],
                "bs": [spec["b"]], "shifts": (spec["shift"],),
                "acts": ("none",)}
    return None


def fleet_signature(artifact) -> Optional[FleetSignature]:
    """Hashable stacking-compatibility key, or None if unstackable.

    Artifacts sharing a signature can be stacked into one fleet dispatch.
    Eligibility requires the pallas backend (the fleet kernels ARE pallas
    programs), a quantized emit spec (the stacked tensors come from it), a
    single-device artifact (mesh sharding and model stacking are different
    axes — a sharded member keeps its own dispatch), and — for multi-stage
    families (MLP, SVM) — the megakernel routing, since a member that fell
    back to per-layer dispatch exceeds the VMEM budget alone and can only
    be worse stacked.
    """
    if artifact.target.backend != "pallas":
        return None
    if artifact.mesh is not None or artifact.replicas != 1:
        return None
    spec = artifact.extras.get("emit_spec")
    if not spec:
        return None
    family = spec["family"]
    if family in ("mlp", "linear"):
        if family == "mlp" and artifact.kernel_strategy != "megakernel":
            return None
        m = _mlp_spec(artifact)
        fmts = (m["in_fmt"],) + tuple(m["out_fmts"])
        bits = {f.total_bits for f in fmts}
        if len(bits) != 1:  # mixed containers: the stack has no one dtype
            return None
        widths = (int(m["ws"][0].shape[0]),) + tuple(
            int(w.shape[1]) for w in m["ws"])
        return ("mlp", bits.pop(), widths)
    if family == "svm":
        if artifact.kernel_strategy != "megakernel":
            return None
        if spec["fmt"].total_bits != spec["out_fmt"].total_bits:
            return None
        sv, dual = spec["sv"], spec["dual"]
        return ("svm", spec["kernel"], spec["fmt"].total_bits,
                (int(sv.shape[0]), int(sv.shape[1]), int(dual.shape[1])))
    return None  # trees, LMs, float targets: no stacked program exists


@dataclasses.dataclass
class FleetStack:
    """E compatible artifacts fused into one stacked predict program.

    ``predict_device(x)`` runs the single stacked dispatch on ``x`` —
    shared ``(M, F)`` rows or per-slot ``(E, M, F)`` rows (the coalescer's
    staging buffer) — and returns the *unmaterialized* ``(E, M)`` device
    array — the coalescer overlaps the next round's host assembly with
    this round's device compute by deferring the ``np.asarray`` force.
    ``predict(x)`` is the blocking convenience wrapper.  Slot ``e`` of the
    output is bit-identical to ``members[e]``'s own ``predict(x)``; that
    contract is what lets the serving layer scatter rows back to each
    endpoint's futures against its existing golden vectors.
    """

    signature: FleetSignature
    members: Tuple  # the member artifacts' cache keys, in slot order
    n_models: int
    n_features: int
    _predict_device: Callable[[np.ndarray], Any] = dataclasses.field(repr=False)

    @property
    def cache_key(self) -> Tuple:
        return ("fleet",) + tuple(self.members)

    def predict_device(self, x: np.ndarray) -> Any:
        """One stacked dispatch; returns the async (E, M) device array."""
        with spans.span("repro.predict.call", rows=x.size // x.shape[-1]):
            return self._predict_device(x)

    def predict(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(self.predict_device(x), np.int32)


def _quantizer(in_fmts: Sequence[fxp.FxpFormat], n_models: int):
    """Float input -> (E, M, F) quantized stack.

    Accepts ``(M, F)`` shared rows (every model sees the same batch — the
    broadcast case) or ``(E, M, F)`` per-slot rows (the coalescer's staging
    buffer, one slot per member's micro-batch).  All members sharing one
    input format is the common case (a calibrated fleet over one sensor
    family) — quantize in one shot; heterogeneous formats quantize per
    model.  Either way the values are exactly what each member's own input
    stage produces.
    """
    shared = in_fmts[0] if len(set(in_fmts)) == 1 else None
    fmts = tuple(in_fmts)

    def qstack(xf):
        if xf.ndim == 2:  # shared rows for every model
            if shared is not None:
                return jnp.broadcast_to(fxp.quantize(xf, shared),
                                        (n_models,) + xf.shape)
            return jnp.stack([fxp.quantize(xf, f) for f in fmts])
        if shared is not None:  # (E, M, F) per-slot rows
            return fxp.quantize(xf, shared)
        return jnp.stack([fxp.quantize(xf[e], f)
                          for e, f in enumerate(fmts)])

    return qstack


def _stack_mlp(artifacts) -> Callable[[np.ndarray], Any]:
    specs = [_mlp_spec(a) for a in artifacts]
    n_layers = len(specs[0]["ws"])
    weights = tuple(jnp.stack([jnp.asarray(s["ws"][i]) for s in specs])
                    for i in range(n_layers))
    biases = tuple(jnp.stack([jnp.asarray(s["bs"][i]) for s in specs])
                   for i in range(n_layers))
    schedules = tuple(
        tuple(zip(s["shifts"], s["out_fmts"], s["acts"])) for s in specs)
    qstack = _quantizer([s["in_fmt"] for s in specs], len(specs))

    # One jitted program per input shape (the serving buckets are a small
    # closed ladder).  The dispatch-count gates measure a FRESH stack's
    # trace — the fleet op ticks the counter once while tracing, exactly
    # like the per-model megakernel gates in tests/test_megakernel.py.
    @jax.jit
    def forward(xf):
        out = ops.fxp_mlp_fleet(qstack(xf), weights, biases, schedules)
        return jnp.argmax(out, -1).astype(jnp.int32)

    def predict_device(x):
        return forward(jnp.asarray(x, jnp.float32))

    return predict_device


def _stack_svm(artifacts) -> Callable[[np.ndarray], Any]:
    specs = [a.extras["emit_spec"] for a in artifacts]
    kind = specs[0]["kernel"]
    sv = jnp.stack([jnp.asarray(s["sv"]) for s in specs])
    dual = jnp.stack([jnp.asarray(s["dual"]) for s in specs])
    icept = jnp.stack([jnp.asarray(s["b"]) for s in specs])
    params = tuple((s["fmt"], s["out_fmt"], s["qgamma"], s["qcoef0"],
                    s["degree"], s["dec_shift"], s["chain"]) for s in specs)
    qstack = _quantizer([s["fmt"] for s in specs], len(specs))

    @jax.jit
    def forward(xf):
        out = ops.fxp_svm_fleet(qstack(xf), sv, dual, icept, kind, params)
        return jnp.argmax(out, -1).astype(jnp.int32)

    def predict_device(x):
        return forward(jnp.asarray(x, jnp.float32))

    return predict_device


def stack_fleet(artifacts: Sequence[Any]) -> FleetStack:
    """Fuse ``artifacts`` (all sharing one :func:`fleet_signature`) into a
    :class:`FleetStack`.  Raises ``ValueError`` for empty/incompatible
    input or a stack whose minimal model-block cannot fit VMEM."""
    arts: List[Any] = list(artifacts)
    if len(arts) < 2:
        raise ValueError("a fleet needs at least 2 member artifacts")
    sigs = [fleet_signature(a) for a in arts]
    if sigs[0] is None or any(s != sigs[0] for s in sigs):
        raise ValueError(f"artifacts are not fleet-compatible: {sigs}")
    sig = sigs[0]
    if sig[0] == "mlp":
        family, bits, widths = sig
        if not fxp_model.mlp_fleet_fits_vmem(1, widths, bits):
            raise ValueError(
                f"one stacked model-block of widths {widths} at w{bits} "
                f"exceeds the VMEM budget; fleet stacking is not viable")
        predict_device = _stack_mlp(arts)
        n_features = widths[0]
    else:
        _, kernel, bits, (s_, f_, c_) = sig
        if not fxp_model.svm_fleet_fits_vmem(1, s_, f_, c_, bits):
            raise ValueError(
                f"one stacked {kernel}-SVM model-block (S={s_}, F={f_}, "
                f"C={c_}, w{bits}) exceeds the VMEM budget")
        predict_device = _stack_svm(arts)
        n_features = f_
    return FleetStack(signature=sig,
                      members=tuple(a.cache_key for a in arts),
                      n_models=len(arts), n_features=n_features,
                      _predict_device=predict_device)
