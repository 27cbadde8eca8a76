"""Pallas TPU megakernels: the whole fixed-point model in ONE dispatch.

EmbML's classifiers are KB-scale (the paper's Tables report hundreds of
bytes to tens of KB), while VMEM is MB-scale — so for every model this
repo actually serves, *all* packed weights fit on-chip at once.  The
per-layer fused kernel (:mod:`.fxp_layer`) still pays one dispatch per
layer with inter-layer activations round-tripping HBM; at serving batch
sizes that makes the forward pass dispatch-bound, not compute-bound.

The kernels here collapse the entire forward pass into a single
``pallas_call``:

* **MLP** (:func:`fxp_mlp_model_pallas`) — grid = (M/bm,) over the batch
  only; every layer's weight and bias ride in whole (they are KB-scale, no
  K/N blocking needed), and the kernel body unrolls a *static layer
  schedule* of ``(shift, out_format, activation)`` triples frozen from the
  artifact's QuantPlan.  Each layer is the same int32 MXU dot +
  ``requantize``/``qadd``/PWL epilogue the per-layer kernel traces — from
  the same shared :mod:`repro.core.fixedpoint` / activation definitions —
  so megakernel == per-layer fused == chained, bit for bit.  Inter-layer
  activations never leave VMEM.
* **kernel-SVM** (:func:`fxp_svm_model_pallas`) — kernel evaluation
  (x·svᵀ plus the poly/rbf elementwise algebra, including the in-kernel
  squared norms for rbf) and the fused decision matmul + intercept, in one
  body.  Collapses the previous 2-dispatch pallas path
  (``fxp_qmatmul`` + ``fxp_layer``) to 1.

Accumulator contract: identical to :mod:`.fxp_layer` — int32 MXU
accumulation, bit-exact vs the wide-accumulating oracle whenever the true
dot-product magnitude stays below 2^31 (always at these model scales).

**Fit predicate + fallback.**  :func:`mlp_fits_vmem` /
:func:`svm_fits_vmem` bound the kernel's resident working set (packed
weights + a worst-case batch block of int32 intermediates) against
:func:`vmem_budget`; the mlp/svm lowerings consult them and fall back to
the per-layer fused path when a model does not fit.  The budget can be
overridden (or zeroed, forcing the per-layer path everywhere) with the
``REPRO_MEGAKERNEL_VMEM`` environment variable — tests and benchmarks use
that to exercise the fallback without constructing an MB-scale model.

Zero padding is bit-safe by construction: padded input feature columns
meet zero weight rows; padded hidden lanes carry a nonzero ``sigmoid(0)``
but feed zero rows of the next layer's weights; padded support-vector rows
meet zero dual-coefficient rows; padded output columns are sliced off
before the argmax.  Integer addition is associative and commutative, so
the (order-preserving) padded reductions change no bit of the logical
slice.

The pure-jnp oracles are :func:`repro.kernels.ref.fxp_mlp_model_ref` and
:func:`repro.kernels.ref.fxp_svm_model_ref`.
"""

from __future__ import annotations

import functools
import os
from itertools import chain
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import fixedpoint
from repro.core.activations import get_qsigmoid
from repro.core.fixedpoint import FxpFormat

from .fxp_layer import LAYER_ACTIVATIONS
from .mosaic import index_map, mxu_dot, vpu_format
from . import tune
from .tune import _VMEM_BUDGET

__all__ = ["fxp_mlp_model_pallas", "fxp_svm_model_pallas", "LayerSchedule",
           "mlp_fits_vmem", "svm_fits_vmem", "vmem_budget", "SVM_KERNELS",
           "fxp_mlp_fleet_pallas", "fxp_svm_fleet_pallas", "FleetSchedules",
           "SvmFleetParams", "mlp_fleet_fits_vmem", "svm_fleet_fits_vmem",
           "mlp_fleet_vmem_bytes", "svm_fleet_vmem_bytes"]

# One entry per layer: (requantization shift, output format, activation).
LayerSchedule = Tuple[Tuple[int, FxpFormat, str], ...]
# One LayerSchedule per stacked model (fleet kernels).
FleetSchedules = Tuple[LayerSchedule, ...]
# One per stacked SVM: (fmt, out_fmt, qgamma, qcoef0, degree, dec_shift).
SvmFleetParams = Tuple[Tuple[FxpFormat, FxpFormat, int, int, int, int], ...]

SVM_KERNELS = ("poly", "rbf")

_LANE = 128  # Mosaic minor-dim tile (every container width)


# --------------------------------------------------------------------------
# VMEM-fit predicate (the megakernel / per-layer routing decision)
# --------------------------------------------------------------------------
def vmem_budget() -> int:
    """Byte budget for one megakernel grid step's resident working set.

    ``REPRO_MEGAKERNEL_VMEM`` overrides (``0`` disables the megakernel
    everywhere — the benchmark's per-layer baseline and the fallback tests
    force the routing this way); the default is the same budget the
    block-size autotuner steers under.
    """
    env = os.environ.get("REPRO_MEGAKERNEL_VMEM")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            pass
    return _VMEM_BUDGET


def _padded_dim(d: int) -> int:
    """Feature-dim size as the kernel sees it (lane-tiled on real TPU)."""
    if tune.on_tpu():
        return -(-int(d) // _LANE) * _LANE
    return int(d)


def _limb_bytes(bits: int) -> int:
    """Kernel temporaries per element of a weight operand: a 16-bit dot
    splits it in the kernel (an int32 copy and three int8 limbs, see
    ``mosaic.mxu_dot``); an 8-bit one feeds the MXU as it is."""
    return 7 if int(bits) == 16 else 0


def mlp_vmem_bytes(widths: Sequence[int], bits: int, bm: int = 128) -> int:
    """Worst-case resident bytes of one MLP megakernel grid step.

    ``widths`` = [n_features, hidden..., n_classes] (logical; padded to the
    TPU tile when relevant).  Counts every layer's packed weight + bias and
    the batch block of inputs/outputs, each double-buffered by the
    pipeline; the weights' limb temporaries; and three ``bm x max_width``
    int32 intermediates (accumulator + the epilogue's widened
    temporaries).
    """
    dims = [_padded_dim(d) for d in widths]
    e = max(1, int(bits) // 8)
    weights = sum(i * o for i, o in zip(dims, dims[1:]))
    biases = sum(dims[1:])
    io = bm * (dims[0] + dims[-1])
    scratch = 3 * bm * max(dims) * 4
    return (2 * (weights + biases + io) * e + weights * _limb_bytes(bits)
            + scratch)


def svm_vmem_bytes(n_sv: int, n_feat: int, n_classes: int, bits: int,
                   bm: int = 128) -> int:
    """Worst-case resident bytes of one SVM megakernel grid step: the
    double-buffered operands, the limb temporaries of both dots' weights,
    the rbf norm's int32 squares of the support vectors (two more copies
    for the 16-bit halves), and the (bm, n_sv) int32 intermediates."""
    s, f, c = (_padded_dim(d) for d in (n_sv, n_feat, n_classes))
    e = max(1, int(bits) // 8)
    weights = s * f + s * c + c
    io = bm * (f + c)
    squares = s * f * (12 if int(bits) == 16 else 4)
    # The (bm, n_sv) kernel-value matrix dominates the intermediates: the
    # int32 dot accumulator plus the widened elementwise chain.
    scratch = 3 * bm * max(s, f, c) * 4
    return (2 * (weights + io) * e + (s * f + s * c) * _limb_bytes(bits)
            + squares + scratch)


def mlp_fits_vmem(widths: Sequence[int], bits: int, bm: int = 128) -> bool:
    return mlp_vmem_bytes(widths, bits, bm) <= vmem_budget()


def svm_fits_vmem(n_sv: int, n_feat: int, n_classes: int, bits: int,
                  bm: int = 128) -> bool:
    return svm_vmem_bytes(n_sv, n_feat, n_classes, bits, bm) <= vmem_budget()


def mlp_fleet_vmem_bytes(n_models: int, widths: Sequence[int], bits: int,
                         bm: int = 128) -> int:
    """Worst-case resident bytes of one MLP *fleet* grid step: ``n_models``
    stacked copies of a single-model step (every member's weights, the
    model-block of inputs/outputs, and the widened intermediates all carry
    the leading model axis)."""
    return int(n_models) * mlp_vmem_bytes(widths, bits, bm)


def svm_fleet_vmem_bytes(n_models: int, n_sv: int, n_feat: int,
                         n_classes: int, bits: int, bm: int = 128) -> int:
    """Worst-case resident bytes of one SVM *fleet* grid step."""
    return int(n_models) * svm_vmem_bytes(n_sv, n_feat, n_classes, bits, bm)


def mlp_fleet_fits_vmem(n_models: int, widths: Sequence[int], bits: int,
                        bm: int = 128) -> bool:
    """Whether a model-block of ``n_models`` stacked MLPs fits the budget
    (the fleet-stacking eligibility check; ``n_models`` is the model-axis
    block, not necessarily the whole fleet — the tuner may split it)."""
    return mlp_fleet_vmem_bytes(n_models, widths, bits, bm) <= vmem_budget()


def svm_fleet_fits_vmem(n_models: int, n_sv: int, n_feat: int,
                        n_classes: int, bits: int, bm: int = 128) -> bool:
    return (svm_fleet_vmem_bytes(n_models, n_sv, n_feat, n_classes, bits, bm)
            <= vmem_budget())


# --------------------------------------------------------------------------
# MLP megakernel
# --------------------------------------------------------------------------
def _mlp_kernel(*refs, schedule: LayerSchedule):
    # refs = (x, w0, b0, w1, b1, ..., out); the layer loop is a *Python*
    # loop over the static schedule — fully unrolled at trace time, so the
    # whole forward pass is one kernel body with h resident in VMEM.
    x_ref, o_ref = refs[0], refs[-1]
    wb = refs[1:-1]
    h = x_ref[...]
    for (shift, fmt, activation), w_ref, b_ref in zip(
            schedule, wb[0::2], wb[1::2]):
        # Same shared epilogue definitions as fxp_layer._kernel: the
        # megakernel cannot drift from the per-layer fused (or chained)
        # semantics because all three trace the same functions.
        h = _mlp_layer_step(h, w_ref[...], b_ref[...], shift, fmt,
                            activation, batched=False)
    o_ref[...] = h


@functools.partial(jax.jit, static_argnames=("schedule", "bm", "interpret"))
def fxp_mlp_model_pallas(x: jax.Array, weights: Tuple[jax.Array, ...],
                         biases: Tuple[jax.Array, ...],
                         schedule: LayerSchedule, bm: int = 128,
                         interpret: bool = False) -> jax.Array:
    """The whole MLP forward in one ``pallas_call``.

    x: (M, K0); weights[i]: (K_i, K_{i+1}); biases[i]: (K_{i+1},) — all
    whole (the fit predicate guarantees they are VMEM-resident), batch
    blocked by ``bm`` (M % bm == 0; the ``ops.py`` wrapper pads).
    ``schedule`` is the static per-layer (shift, out_format, activation)
    plan; the output is in the last layer's format.
    """
    if not (len(weights) == len(biases) == len(schedule) >= 1):
        raise ValueError("weights/biases/schedule must align, >= 1 layer")
    for _, fmt, activation in schedule:
        if activation not in LAYER_ACTIVATIONS:
            raise KeyError(f"activation must be one of {LAYER_ACTIVATIONS}")
    m, k0 = x.shape
    assert m % bm == 0, (x.shape, bm)
    out_fmt = schedule[-1][1]
    n_out = weights[-1].shape[1]

    # Biases ride as (1, N) rows: a rank-1 block is not a Mosaic tile.
    biases = tuple(b.reshape(1, -1) for b in biases)
    whole = index_map(lambda i: (0, 0))
    in_specs = [pl.BlockSpec((bm, k0), index_map(lambda i: (i, 0)))]
    for w, b in zip(weights, biases):
        in_specs.append(pl.BlockSpec(w.shape, whole))
        in_specs.append(pl.BlockSpec(b.shape, whole))

    return pl.pallas_call(
        functools.partial(_mlp_kernel, schedule=schedule),
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n_out), index_map(lambda i: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((m, n_out), out_fmt.dtype),
        interpret=interpret,
    )(x, *chain.from_iterable(zip(weights, biases)))


# --------------------------------------------------------------------------
# kernel-SVM megakernel (kernel evaluation + vote, one dispatch)
# --------------------------------------------------------------------------
def _qsq_norm(qv, fmt: FxpFormat, vfmt: FxpFormat):
    """Row sums of squares, one rounded shift: the rbf norm terms, as a
    (..., 1) column.

    The reference sums exactly (int64).  int32 holds that sum exactly for
    8-bit values (K < 2^17 terms of at most 2^14).  16-bit squares reach
    2^30, so they are summed as 16-bit halves: while the high half's sum
    stays below 2^14 the recombined total fits int32 exactly, and past it
    the total is at least 2^30, which saturates every 16-bit format.
    """
    w = qv.astype(jnp.int32)
    sq = w * w
    if fmt.total_bits == 8:
        acc = jnp.sum(sq, axis=-1, keepdims=True, dtype=jnp.int32)
    else:
        assert qv.shape[-1] < (1 << 14), qv.shape
        hi = jnp.sum(sq >> jnp.int32(16), axis=-1, keepdims=True,
                     dtype=jnp.int32)
        lo = jnp.sum(sq & jnp.int32(0xFFFF), axis=-1, keepdims=True,
                     dtype=jnp.int32)
        acc = jnp.where(hi >= jnp.int32(1 << 14),
                        jnp.full_like(hi, jnp.iinfo(jnp.int32).max),
                        (hi << jnp.int32(16)) + lo)
    return fixedpoint.rshift_round_saturate(acc, vfmt)


def _svm_decision(qx, qsv, dual, icept, *, kind: str, fmt: FxpFormat,
                  out_fmt: FxpFormat, qgamma: int, qcoef0: int, degree: int,
                  dec_shift: int, batched: bool):
    """The whole decision function on (bm, F) values -> (bm, C), or on
    model-stacked (be, bm, F) -> (be, bm, C) when ``batched`` (the model
    axis rides as a dot_general batch dimension; models never mix).
    ``icept`` is (1, C) — or (be, 1, C) — so it broadcasts over rows.

    One spelling of the algebra for the single-model kernel, the uniform
    fleet and the fleet's per-model branches — one bit-identity contract.
    """
    vfmt, vout = vpu_format(fmt), vpu_format(out_fmt)
    if batched:
        sv_dims = (((2,), (2,)), ((0,), (0,)))
        dual_dims = (((2,), (1,)), ((0,), (0,)))
    else:
        sv_dims = (((1,), (1,)), ((), ()))
        dual_dims = (((1,), (0,)), ((), ()))
    # x . sv^T without materializing the transpose: contract the shared
    # feature axis.  Integer dot == fxp_qmatmul's accumulate, then the
    # single-format requantize (input/sv/kernel share one plan group).
    dot = mxu_dot(qx, qsv, sv_dims)
    dot = fixedpoint.requantize(dot, fmt.frac_bits, vfmt)
    g = jnp.asarray(qgamma, fmt.dtype)
    if kind == "poly":
        k = fixedpoint.qadd(fixedpoint.qmul(dot, g, vfmt),
                            jnp.asarray(qcoef0, fmt.dtype), vfmt)
        k = fixedpoint.qpow_int(k, degree, vfmt)
    else:  # rbf
        x2 = _qsq_norm(qx, fmt, vfmt)  # (..., bm, 1) column
        sv2 = jnp.swapaxes(_qsq_norm(qsv, fmt, vfmt), -1, -2)  # (..., 1, S)
        d2 = fixedpoint.qadd(
            fixedpoint.qsub(x2, fixedpoint.qadd(dot, dot, vfmt), vfmt),
            sv2, vfmt)
        arg = fixedpoint.qneg(fixedpoint.qmul(d2, g, vfmt), vfmt)
        k = fixedpoint.qexp(arg, vfmt)
    # Decision stage: the fused-layer epilogue (k @ dual, cross-format
    # shift, saturating intercept add) still inside the same kernel body.
    acc = mxu_dot(k, dual, dual_dims)
    out = fixedpoint.requantize(acc, dec_shift, vout)
    out = fixedpoint.qadd(out, icept, vout)
    return out.astype(out_fmt.dtype)


def _svm_kernel(x_ref, sv_ref, dual_ref, icept_ref, o_ref, *, kind: str,
                fmt: FxpFormat, out_fmt: FxpFormat, qgamma: int, qcoef0: int,
                degree: int, dec_shift: int):
    o_ref[...] = _svm_decision(
        x_ref[...], sv_ref[...], dual_ref[...], icept_ref[...], kind=kind,
        fmt=fmt, out_fmt=out_fmt, qgamma=qgamma, qcoef0=qcoef0,
        degree=degree, dec_shift=dec_shift, batched=False)


@functools.partial(jax.jit, static_argnames=(
    "kind", "fmt", "out_fmt", "qgamma", "qcoef0", "degree", "dec_shift",
    "bm", "interpret"))
def fxp_svm_model_pallas(qx: jax.Array, sv: jax.Array, dual: jax.Array,
                         icept: jax.Array, kind: str, fmt: FxpFormat,
                         out_fmt: FxpFormat, qgamma: int, qcoef0: int,
                         degree: int, dec_shift: int, bm: int = 128,
                         interpret: bool = False) -> jax.Array:
    """The whole kernel-SVM decision function in one ``pallas_call``.

    qx: (M, F); sv: (S, F) (un-transposed support vectors); dual: (S, C);
    icept: (C,) — support vectors/duals ride whole, batch blocked by ``bm``.
    ``qgamma``/``qcoef0`` are the *quantized integer* constants (static, so
    they trace as kernel immediates); ``dec_shift`` is the decision stage's
    cross-format requantization (``m_k + m_dual - m_out``).
    """
    if kind not in SVM_KERNELS:
        raise KeyError(f"kind must be one of {SVM_KERNELS}")
    m, f = qx.shape
    s, c = dual.shape
    assert sv.shape == (s, f) and icept.shape == (c,), \
        (qx.shape, sv.shape, dual.shape, icept.shape)
    assert m % bm == 0, (qx.shape, bm)

    kernel = functools.partial(
        _svm_kernel, kind=kind, fmt=fmt, out_fmt=out_fmt, qgamma=qgamma,
        qcoef0=qcoef0, degree=int(degree), dec_shift=int(dec_shift))
    rows = index_map(lambda i: (i, 0))
    whole = index_map(lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, f), rows),
            pl.BlockSpec((s, f), whole),
            pl.BlockSpec((s, c), whole),
            pl.BlockSpec((1, c), whole),
        ],
        out_specs=pl.BlockSpec((bm, c), rows),
        out_shape=jax.ShapeDtypeStruct((m, c), out_fmt.dtype),
        interpret=interpret,
    )(qx, sv, dual, icept.reshape(1, c))


# --------------------------------------------------------------------------
# Fleet kernels: E stacked models, ONE dispatch
# --------------------------------------------------------------------------
# Every operand gains a leading model axis and the grid iterates (model
# blocks, batch blocks).  Two regimes:
#
# * **uniform** — every stacked model shares one LayerSchedule (fixed-format
#   fleets: same shifts, formats, activations).  The kernel batches the MXU
#   dot over the model axis (`be` models per grid step) and the shared
#   epilogue applies elementwise — identical math to `be` single-model
#   steps, one grid traversal.
# * **heterogeneous** — calibrated fleets where each member froze its own
#   shift/format schedule.  The model block is 1 and the kernel selects the
#   member's *static* branch with ``jax.lax.switch`` over the distinct
#   schedules (one traced branch per unique schedule, picked by the grid's
#   model index) — per-model static arguments without per-model dispatches.
#
# Bit-safety of stacking mirrors single-model padding: models never mix
# (the dot's batch/model axis never contracts), so slot e of the output is
# exactly what model e's single dispatch computes.
def _uniq_branches(items) -> Tuple[list, list]:
    """Distinct entries (first-seen order) + the static model->entry map."""
    uniq = []
    for it in items:
        if it not in uniq:
            uniq.append(it)
    return uniq, [uniq.index(it) for it in items]


def _branch_index(indices) -> "jnp.ndarray":
    """Traced branch index for the current grid step's model.

    ``indices[e]`` is model e's (static) branch; pallas kernels cannot
    capture array constants, so the lookup is an unrolled scalar
    ``where``-chain over the grid's model index — fleets are small (tens
    of members), the chain folds to a handful of scalar selects.
    """
    pid = pl.program_id(0)
    idx = jnp.int32(0)
    for e_i, u_i in enumerate(indices):
        if u_i != 0:
            idx = jnp.where(pid == e_i, jnp.int32(u_i), idx)
    return idx


def _mlp_layer_step(h, w, b, shift: int, fmt: FxpFormat, activation: str,
                    batched: bool):
    """One fused layer on (bm, K) values — or (be, bm, K) when ``batched``,
    contracting K with the model axis as a dot_general batch dim.  ``b`` is
    the (1, N) — or (be, 1, N) — bias row."""
    if batched:
        dims = (((2,), (1,)), ((0,), (0,)))
    else:
        dims = (((1,), (0,)), ((), ()))
    vfmt = vpu_format(fmt)
    acc = mxu_dot(h, w, dims)
    h = fixedpoint.requantize(acc, shift, vfmt)
    h = fixedpoint.qadd(h, b, vfmt)
    if activation != "none":
        h = get_qsigmoid(activation)(h, vfmt)
    return h.astype(fmt.dtype)


def _mlp_fleet_kernel(*refs, schedules: FleetSchedules, be: int):
    # refs = (x, w0, b0, ..., out); every block carries a leading model axis
    # of size ``be``.
    x_ref, o_ref = refs[0], refs[-1]
    wb = refs[1:-1]
    uniq, indices = _uniq_branches(schedules)
    if len(uniq) == 1:
        # Uniform schedule: batch the dot over the model axis; the static
        # layer loop unrolls exactly like the single-model megakernel.
        h = x_ref[...]
        for (shift, fmt, act), w_ref, b_ref in zip(uniq[0], wb[0::2],
                                                   wb[1::2]):
            h = _mlp_layer_step(h, w_ref[...], b_ref[...], shift, fmt, act,
                                batched=True)
        o_ref[...] = h
        return
    # Heterogeneous: one model per grid step (be == 1), one branch per
    # distinct schedule, selected by the model index — static per-model
    # schedules without per-model dispatches.
    n = len(wb) // 2

    def _branch(sched: LayerSchedule):
        def run(h, *wb_vals):
            for (shift, fmt, act), w, b in zip(sched, wb_vals[:n],
                                               wb_vals[n:]):
                h = _mlp_layer_step(h, w, b, shift, fmt, act, batched=False)
            return h
        return run

    out = jax.lax.switch(
        _branch_index(indices), [_branch(s) for s in uniq], x_ref[0],
        *[w_ref[0] for w_ref in wb[0::2]],
        *[b_ref[0] for b_ref in wb[1::2]])
    o_ref[...] = out[None]


@functools.partial(jax.jit,
                   static_argnames=("schedules", "be", "bm", "interpret"))
def fxp_mlp_fleet_pallas(x: jax.Array, weights: Tuple[jax.Array, ...],
                         biases: Tuple[jax.Array, ...],
                         schedules: FleetSchedules, be: int = 1,
                         bm: int = 128, interpret: bool = False) -> jax.Array:
    """E stacked MLP forward passes in one ``pallas_call``.

    x: (E, M, K0); weights[i]: (E, K_i, K_{i+1}); biases[i]: (E, K_{i+1});
    ``schedules`` holds model e's static layer plan at index e.  Grid =
    (E/be, M/bm); heterogeneous schedules require ``be == 1`` (the kernel
    switches per-model branches by grid index).  Slot e of the (E, M, C)
    output is bit-identical to model e's own single-model dispatch.
    """
    e, m, k0 = x.shape
    if len(schedules) != e:
        raise ValueError(f"{len(schedules)} schedules for {e} stacked models")
    if not (len(weights) == len(biases) == len(schedules[0]) >= 1):
        raise ValueError("weights/biases/schedules must align, >= 1 layer")
    for sched in schedules:
        if len(sched) != len(schedules[0]):
            raise ValueError("stacked models must share the layer count")
        for _, fmt, activation in sched:
            if activation not in LAYER_ACTIVATIONS:
                raise KeyError(
                    f"activation must be one of {LAYER_ACTIVATIONS}")
            if fmt.dtype != schedules[0][0][1].dtype:
                raise ValueError("stacked models must share the container")
    if len(set(schedules)) > 1 and be != 1:
        raise ValueError("heterogeneous schedules require be == 1")
    assert e % be == 0 and m % bm == 0, (x.shape, be, bm)
    out_fmt = schedules[0][-1][1]
    n_out = weights[-1].shape[2]

    # Biases ride as (E, 1, N): the trailing (1, N) block equals the whole
    # trailing dims, which Mosaic tiles for any model block ``be``.
    biases = tuple(b.reshape(e, 1, -1) for b in biases)
    rows = index_map(lambda ei, mi: (ei, mi, 0))
    member = index_map(lambda ei, mi: (ei, 0, 0))
    in_specs = [pl.BlockSpec((be, bm, k0), rows)]
    for w, b in zip(weights, biases):
        in_specs.append(pl.BlockSpec((be,) + w.shape[1:], member))
        in_specs.append(pl.BlockSpec((be,) + b.shape[1:], member))

    return pl.pallas_call(
        functools.partial(_mlp_fleet_kernel, schedules=schedules, be=be),
        grid=(e // be, m // bm),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((be, bm, n_out), rows),
        out_shape=jax.ShapeDtypeStruct((e, m, n_out), out_fmt.dtype),
        interpret=interpret,
    )(x, *chain.from_iterable(zip(weights, biases)))


def _svm_fleet_kernel(x_ref, sv_ref, dual_ref, icept_ref, o_ref, *,
                      kind: str, params: SvmFleetParams, be: int):
    uniq, indices = _uniq_branches(params)
    if len(uniq) == 1:
        fmt, out_fmt, qgamma, qcoef0, degree, dec_shift = uniq[0]
        o_ref[...] = _svm_decision(
            x_ref[...], sv_ref[...], dual_ref[...], icept_ref[...],
            kind=kind, fmt=fmt, out_fmt=out_fmt, qgamma=qgamma,
            qcoef0=qcoef0, degree=degree, dec_shift=dec_shift, batched=True)
        return

    def _branch(p):
        fmt, out_fmt, qgamma, qcoef0, degree, dec_shift = p

        def run(qx, qsv, dual, icept):
            return _svm_decision(qx, qsv, dual, icept, kind=kind, fmt=fmt,
                                 out_fmt=out_fmt, qgamma=qgamma,
                                 qcoef0=qcoef0, degree=degree,
                                 dec_shift=dec_shift, batched=False)
        return run

    out = jax.lax.switch(
        _branch_index(indices), [_branch(p) for p in uniq], x_ref[0],
        sv_ref[0], dual_ref[0], icept_ref[0])
    o_ref[...] = out[None]


@functools.partial(jax.jit, static_argnames=("kind", "params", "be", "bm",
                                             "interpret"))
def fxp_svm_fleet_pallas(qx: jax.Array, sv: jax.Array, dual: jax.Array,
                         icept: jax.Array, kind: str, params: SvmFleetParams,
                         be: int = 1, bm: int = 128,
                         interpret: bool = False) -> jax.Array:
    """E stacked kernel-SVM decision functions in one ``pallas_call``.

    qx: (E, M, F); sv: (E, S, F); dual: (E, S, C); icept: (E, C); ``params``
    holds model e's static (fmt, out_fmt, qgamma, qcoef0, degree, dec_shift)
    at index e.  Heterogeneous params require ``be == 1``.
    """
    if kind not in SVM_KERNELS:
        raise KeyError(f"kind must be one of {SVM_KERNELS}")
    e, m, f = qx.shape
    s, c = dual.shape[1:]
    if len(params) != e:
        raise ValueError(f"{len(params)} param tuples for {e} stacked models")
    assert sv.shape == (e, s, f) and icept.shape == (e, c), \
        (qx.shape, sv.shape, dual.shape, icept.shape)
    if len(set(params)) > 1 and be != 1:
        raise ValueError("heterogeneous SVM params require be == 1")
    assert e % be == 0 and m % bm == 0, (qx.shape, be, bm)
    out_fmt = params[0][1]

    rows = index_map(lambda ei, mi: (ei, mi, 0))
    member = index_map(lambda ei, mi: (ei, 0, 0))
    return pl.pallas_call(
        functools.partial(_svm_fleet_kernel, kind=kind, params=params,
                          be=be),
        grid=(e // be, m // bm),
        in_specs=[
            pl.BlockSpec((be, bm, f), rows),
            pl.BlockSpec((be, s, f), member),
            pl.BlockSpec((be, s, c), member),
            pl.BlockSpec((be, 1, c), member),
        ],
        out_specs=pl.BlockSpec((be, bm, c), rows),
        out_shape=jax.ShapeDtypeStruct((e, m, c), out_fmt.dtype),
        interpret=interpret,
    )(qx, sv, dual, icept.reshape(e, 1, c))
