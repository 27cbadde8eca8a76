"""Jitted public wrappers around the Pallas kernels.

Each wrapper pads to block multiples, dispatches to the kernel, and
unpads.  :func:`repro.kernels.tune.on_tpu` decides once per call whether
the kernel compiles with Mosaic (lane-padded operands) or runs in interpret
mode (unpadded, on the CPU); the padding helpers take that decision as an
argument.  These are the entry points the rest of the framework uses;
``impl='xla'`` selects the pure-jnp reference instead, which is also how
the dry-run lowers.

Block sizes are no longer fixed 128/256 defaults: matmul-shaped ops consult
the :mod:`repro.kernels.tune` autotuner (shape/dtype-keyed, JSON disk
cache), and every wrapper shares one padding policy — pad each axis up to
the tuned block, slice the logical shape back off the output.  Batch-like
axes are bucketed to powers of two (the serving ladder), so warm buckets
reuse both the tuning entry and the jit trace.

``count_dispatches()`` counts the logical kernel dispatches traced while
active (one per wrapper call — the unit the fused layer kernel collapses
from 3 to 1 per MLP layer).
"""

from __future__ import annotations

import contextlib
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fixedpoint import FxpFormat
from repro.core.trees import TreeArrays
from . import ref as ref_ops
from . import tune
from . import fxp_model
from .flash_attention import flash_attention_pallas
from .fxp_layer import fxp_layer_pallas
from .fxp_model import (fxp_mlp_fleet_pallas, fxp_mlp_model_pallas,
                        fxp_svm_fleet_pallas, fxp_svm_model_pallas)
from .fxp_qmatmul import fxp_qmatmul_pallas
from .pwl_activation import pwl_activation_pallas
from .tree_ensemble import pack_tree, tree_ensemble_pallas, tree_vmem_bytes

__all__ = ["fxp_qmatmul", "fxp_layer", "fxp_mlp_model", "fxp_svm_model",
           "fxp_mlp_fleet", "fxp_svm_fleet", "pwl_activation",
           "tree_predict", "flash_attention", "count_dispatches"]


# --------------------------------------------------------------------------
# dispatch accounting
# --------------------------------------------------------------------------
class DispatchCounter:
    """Counts wrapper-level kernel dispatches (trace-time, per jit trace)."""

    def __init__(self):
        self.count = 0


_active_counters: List[DispatchCounter] = []


def _tick() -> None:
    for c in _active_counters:
        c.count += 1


@contextlib.contextmanager
def count_dispatches():
    """``with count_dispatches() as c: ...`` — ``c.count`` is the number of
    kernel dispatches issued (or traced, under jit) inside the block."""
    c = DispatchCounter()
    _active_counters.append(c)
    try:
        yield c
    finally:
        _active_counters.remove(c)


# --------------------------------------------------------------------------
# the shared padding policy
# --------------------------------------------------------------------------
def _pad_axis(x: jax.Array, axis: int, mult: int, value=0):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value), size


def _pad_matmul(a: jax.Array, b: jax.Array, blocks: tune.Blocks):
    """Pad (M, K) x (K, N) operands to the tuned (bm, bn, bk) multiples."""
    bm, bn, bk = blocks
    ap, m0 = _pad_axis(a, 0, bm)
    ap, _ = _pad_axis(ap, 1, bk)
    bp, _ = _pad_axis(b, 0, bk)
    bp, n0 = _pad_axis(bp, 1, bn)
    return ap, bp, m0, n0


def _timed_runner(make_call):
    """Best-of-3 wall-time of a zero-input kernel call (on-TPU tuning only;
    timing is shape-dependent, not value-dependent, so zeros suffice).

    The wrappers are usually called while an artifact's predict is being
    traced under ``jax.jit``; each candidate is compiled ahead of time as
    a program of its own (zeros and constants inside it), so it runs on
    the device even then.
    """

    def run(blocks: tune.Blocks) -> float:
        call = jax.jit(lambda: make_call(blocks)).lower().compile()
        call().block_until_ready()  # warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            call().block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    return run


def _tuning_operands(m: int, k: int, n: int, dtype,
                     blocks: tune.Blocks):
    """Zero operands shaped exactly as the kernel would see them for these
    blocks — the same bucket-then-pad policy as the real dispatch path, kept
    in one place so the tuner times what the kernel will actually run."""
    bm, bn, bk = blocks
    mb = tune.batch_bucket(m, cap=1 << 30)
    za = jnp.zeros((-(-mb // bm) * bm, -(-k // bk) * bk), dtype)
    zb = jnp.zeros((za.shape[1], -(-n // bn) * bn), dtype)
    return za, zb


def _matmul_tuning(kind: str, m: int, k: int, n: int, fmt: FxpFormat,
                   make_call=None) -> tune.Blocks:
    runner = None
    if make_call is not None and tune.on_tpu():
        runner = _timed_runner(make_call)
    return tune.matmul_blocks(kind, m, k, n, fmt.total_bits, runner)


# --------------------------------------------------------------------------
# ops
# --------------------------------------------------------------------------
def fxp_qmatmul(a: jax.Array, b: jax.Array, fmt: FxpFormat,
                impl: str = "pallas",
                blocks: Optional[tune.Blocks] = None) -> jax.Array:
    """Qn.m matmul.  a: (M, K), b: (K, N) -> (M, N) in ``fmt``; the
    operands are in ``fmt.dtype``, or narrower for a wider accumulator
    format (a 32-bit ``fmt`` with no fractional bits returns the raw int32
    accumulator).

    ``blocks`` overrides the autotuned (bm, bn, bk); pass it to reproduce a
    fixed blocking (e.g. the historical 128/128/256 defaults in benchmarks).
    """
    _tick()
    if impl in ("xla", "ref"):
        return ref_ops.fxp_qmatmul_ref(a, b, fmt)
    (m, k), n = a.shape, b.shape[1]
    if blocks is None:
        def make_call(blk):
            za, zb = _tuning_operands(m, k, n, a.dtype, blk)
            return fxp_qmatmul_pallas(za, zb, fmt, bm=blk[0], bn=blk[1],
                                      bk=blk[2])

        blocks = _matmul_tuning("qmatmul", m, k, n, fmt, make_call)
    bm, bn, bk = blocks
    ap, bp, m0, n0 = _pad_matmul(a, b, blocks)
    out = fxp_qmatmul_pallas(ap, bp, fmt, bm=bm, bn=bn, bk=bk,
                             interpret=not tune.on_tpu())
    return out[:m0, :n0]


def fxp_layer(a: jax.Array, w: jax.Array, bias: jax.Array, fmt: FxpFormat,
              activation: str = "none", shift: Optional[int] = None,
              impl: str = "pallas",
              blocks: Optional[tune.Blocks] = None) -> jax.Array:
    """Fused fixed-point layer: ``act(qadd(qmatmul(a, w), bias))`` in one
    kernel dispatch.  a: (M, K), w: (K, N), bias: (N,) -> (M, N); bias and
    the output are in ``fmt``; ``activation`` is a Qn.m sigmoid name or
    ``"none"`` (logits).  ``shift`` is the mixed-format requantization
    amount (``m_a + m_w - m_out`` from a per-tensor QuantPlan); None keeps
    the single-format semantics where every operand shares ``fmt``.

    Bit-identical to the chained ``fxp_qmatmul`` -> ``qadd`` -> ``qsigmoid``
    path (same epilogue math, traced from the same activation functions);
    on the pallas backend the int32 accumulator stays in VMEM across K and
    the epilogue runs on the VPU — the activations never round-trip HBM.
    """
    _tick()
    if impl in ("xla", "ref"):
        return ref_ops.fxp_layer_ref(a, w, bias, fmt, activation, shift)
    (m, k), n = a.shape, w.shape[1]
    if blocks is None:
        def make_call(blk):
            za, zw = _tuning_operands(m, k, n, a.dtype, blk)
            zb = jnp.zeros((zw.shape[1],), fmt.dtype)
            return fxp_layer_pallas(za, zw, zb, fmt, activation, shift=shift,
                                    bm=blk[0], bn=blk[1], bk=blk[2])

        blocks = _matmul_tuning("layer", m, k, n, fmt, make_call)
    bm, bn, bk = blocks
    ap, wp, m0, n0 = _pad_matmul(a, w, blocks)
    biasp, _ = _pad_axis(bias, 0, bn)
    out = fxp_layer_pallas(ap, wp, biasp, fmt, activation, shift=shift,
                           bm=bm, bn=bn, bk=bk, interpret=not tune.on_tpu())
    return out[:m0, :n0]


_LANE = 128  # Mosaic minor-dim tile: model operand padding on real TPU


def fxp_mlp_model(x: jax.Array, weights, biases,
                  schedule: fxp_model.LayerSchedule, impl: str = "pallas",
                  bm: Optional[int] = None) -> jax.Array:
    """The whole MLP forward — every layer — in ONE kernel dispatch.

    x: (M, K0) in the input format's dtype; ``weights``/``biases`` are the
    per-layer quantized operands; ``schedule`` the static per-layer
    ``(shift, out_format, activation)`` plan (see
    :mod:`repro.kernels.fxp_model`).  Callers are expected to have checked
    :func:`repro.kernels.fxp_model.mlp_fits_vmem` (the lowerings do, and
    fall back to per-layer :func:`fxp_layer` calls when it fails).

    Bit-identical to the per-layer fused path and to the composed ref
    oracle; the batch block consults the whole-model autotuner entry.
    """
    _tick()
    weights, biases = tuple(weights), tuple(biases)
    if impl in ("xla", "ref"):
        return ref_ops.fxp_mlp_model_ref(x, weights, biases, schedule)
    m = x.shape[0]
    dims = (x.shape[1],) + tuple(w.shape[1] for w in weights)
    bits = schedule[0][1].total_bits
    chip = tune.on_tpu()
    if bm is None:
        runner = None
        if chip:
            def make_call(blk):
                zx, zws, zbs = _padded_model_operands(
                    jnp.zeros((tune.batch_bucket(m, cap=1 << 30), dims[0]),
                              x.dtype),
                    weights, biases, chip)
                return fxp_mlp_model_pallas(zx, zws, zbs, schedule, bm=blk)

            runner = _timed_runner(make_call)
        bm = tune.model_block_m(
            "mlp", m, dims, bits,
            vmem_bytes=lambda b: fxp_model.mlp_vmem_bytes(dims, bits, b),
            budget=fxp_model.vmem_budget(), runner=runner)
    xp, m0 = _pad_axis(x, 0, bm)
    xp, wp, bp = _padded_model_operands(xp, weights, biases, chip)
    n0 = weights[-1].shape[1]
    out = fxp_mlp_model_pallas(xp, wp, bp, schedule, bm=bm,
                               interpret=not chip)
    return out[:m0, :n0]


def _padded_model_operands(x, weights, biases, chip: bool):
    """Lane-tile the megakernel's feature axes for the chip (``chip`` =
    :func:`tune.on_tpu`; a no-op for interpret mode, which has no tile
    floors and where padding is pure waste).

    Zero padding is bit-safe end to end — padded feature columns meet zero
    weight rows, padded hidden lanes feed zero rows of the next layer, and
    the wrapper slices padded outputs off before anyone can read them.
    """
    if not chip:
        return x, tuple(weights), tuple(biases)
    xp, _ = _pad_axis(x, 1, _LANE)
    ws, bs = [], []
    for w, b in zip(weights, biases):
        wpad, _ = _pad_axis(w, 0, _LANE)
        wpad, _ = _pad_axis(wpad, 1, _LANE)
        bpad, _ = _pad_axis(b, 0, _LANE)
        ws.append(wpad)
        bs.append(bpad)
    return xp, tuple(ws), tuple(bs)


def fxp_svm_model(qx: jax.Array, sv: jax.Array, dual: jax.Array,
                  icept: jax.Array, kind: str, fmt: FxpFormat,
                  out_fmt: FxpFormat, qgamma: int, qcoef0: int, degree: int,
                  dec_shift: int, impl: str = "pallas",
                  bm: Optional[int] = None,
                  chain: Optional[fxp_model.RbfChain] = None) -> jax.Array:
    """The whole kernel-SVM decision function in ONE kernel dispatch:
    x·svᵀ, the poly/rbf elementwise algebra, and the decision matmul +
    intercept (see :mod:`repro.kernels.fxp_model`).  ``sv`` is the
    un-transposed (S, F) matrix; ``qgamma``/``qcoef0`` the quantized
    integer constants; ``chain`` the calibrated rbf's formats.  Collapses
    the previous fxp_qmatmul + fxp_layer pallas path (2 dispatches) to 1;
    bit-identical to it and to :func:`repro.kernels.ref.fxp_svm_model_ref`.
    """
    _tick()
    if impl in ("xla", "ref"):
        return ref_ops.fxp_svm_model_ref(qx, sv, dual, icept, kind, fmt,
                                         out_fmt, qgamma, qcoef0, degree,
                                         dec_shift, chain)
    m, n_feat = qx.shape
    n_sv, n_cls = dual.shape
    bits = fmt.total_bits
    chip = tune.on_tpu()
    if bm is None:
        runner = None
        if chip:
            def make_call(blk):
                zx, zsv, zd, zi = _padded_svm_operands(
                    jnp.zeros((tune.batch_bucket(m, cap=1 << 30), n_feat),
                              qx.dtype), sv, dual, icept, chip)
                return fxp_svm_model_pallas(zx, zsv, zd, zi, kind, fmt,
                                            out_fmt, qgamma, qcoef0, degree,
                                            dec_shift, chain, bm=blk)

            runner = _timed_runner(make_call)
        bm = tune.model_block_m(
            f"svm-{kind}", m, (n_feat, n_sv, n_cls), bits,
            vmem_bytes=lambda b: fxp_model.svm_vmem_bytes(
                n_sv, n_feat, n_cls, bits, b),
            budget=fxp_model.vmem_budget(), runner=runner)
    xp, m0 = _pad_axis(qx, 0, bm)
    xp, svp, dp, ip = _padded_svm_operands(xp, sv, dual, icept, chip)
    out = fxp_svm_model_pallas(xp, svp, dp, ip, kind, fmt, out_fmt, qgamma,
                               qcoef0, degree, dec_shift, chain, bm=bm,
                               interpret=not chip)
    return out[:m0, :n_cls]


def _padded_svm_operands(qx, sv, dual, icept, chip: bool):
    """Lane-tile the SVM megakernel operands for the chip (no-op for
    interpret mode).

    Padded support-vector *rows* produce nonzero kernel values (e.g. the
    rbf kernel of an all-zero vector), but their dual-coefficient rows are
    zero, so they contribute nothing to the decision — zero padding stays
    bit-safe.
    """
    if not chip:
        return qx, sv, dual, icept
    xp, _ = _pad_axis(qx, 1, _LANE)
    svp, _ = _pad_axis(sv, 0, _LANE)
    svp, _ = _pad_axis(svp, 1, _LANE)
    dp, _ = _pad_axis(dual, 0, _LANE)
    dp, _ = _pad_axis(dp, 1, _LANE)
    ip, _ = _pad_axis(icept, 0, _LANE)
    return xp, svp, dp, ip


def fxp_mlp_fleet(x: jax.Array, weights, biases, schedules,
                  impl: str = "pallas", be: Optional[int] = None,
                  bm: Optional[int] = None) -> jax.Array:
    """E stacked MLP forward passes — the whole *fleet* — in ONE dispatch.

    x: (E, M, K0); ``weights[i]``/``biases[i]`` carry the leading model
    axis; ``schedules[e]`` is model e's static layer plan (heterogeneous
    plans are legal — the kernel branches per model).  Slot e of the
    output is bit-identical to model e's own :func:`fxp_mlp_model` call;
    the (be, bm) blocking consults the fleet autotuner entry.
    """
    _tick()
    weights, biases = tuple(weights), tuple(biases)
    schedules = tuple(schedules)
    if impl in ("xla", "ref"):
        return ref_ops.fxp_mlp_fleet_ref(x, weights, biases, schedules)
    e, m, k0 = x.shape
    dims = (k0,) + tuple(int(w.shape[2]) for w in weights)
    bits = schedules[0][0][1].total_bits
    uniform = len(set(schedules)) == 1
    if be is None or bm is None:
        tbe, tbm = tune.fleet_blocks(
            "mlp", e, m, dims, bits, uniform=uniform,
            vmem_bytes=lambda eb, b: fxp_model.mlp_fleet_vmem_bytes(
                eb, dims, bits, b),
            budget=fxp_model.vmem_budget())
        be = tbe if be is None else be
        bm = tbm if bm is None else bm
    if not uniform:
        be = 1
    xp, m0 = _pad_axis(x, 1, bm)
    # Pad the model axis to the block multiple: padded slots run the first
    # member's (static, uniform) schedule on zero weights and are sliced
    # off — same bit-safety argument as batch padding.
    rem = (-e) % be
    if rem:
        xp, _ = _pad_axis(xp, 0, be)
        weights = tuple(_pad_axis(w, 0, be)[0] for w in weights)
        biases = tuple(_pad_axis(b, 0, be)[0] for b in biases)
        schedules = schedules + (schedules[0],) * rem
    chip = tune.on_tpu()
    xp, wp, bp = _padded_fleet_mlp_operands(xp, weights, biases, chip)
    out = fxp_mlp_fleet_pallas(xp, wp, bp, schedules, be=be, bm=bm,
                               interpret=not chip)
    return out[:e, :m0, :dims[-1]]


def _padded_fleet_mlp_operands(x, weights, biases, chip: bool):
    """Lane-tile the fleet megakernel's feature axes for the chip (no-op
    for interpret mode) — the model axis is never tiled, only the trailing
    feature dims."""
    if not chip:
        return x, tuple(weights), tuple(biases)
    xp, _ = _pad_axis(x, 2, _LANE)
    ws, bs = [], []
    for w, b in zip(weights, biases):
        wpad, _ = _pad_axis(w, 1, _LANE)
        wpad, _ = _pad_axis(wpad, 2, _LANE)
        bpad, _ = _pad_axis(b, 1, _LANE)
        ws.append(wpad)
        bs.append(bpad)
    return xp, tuple(ws), tuple(bs)


def fxp_svm_fleet(qx: jax.Array, sv: jax.Array, dual: jax.Array,
                  icept: jax.Array, kind: str, params,
                  impl: str = "pallas", be: Optional[int] = None,
                  bm: Optional[int] = None) -> jax.Array:
    """E stacked kernel-SVM decision functions in ONE dispatch.

    qx: (E, M, F); sv: (E, S, F); dual: (E, S, C); icept: (E, C);
    ``params[e]`` = model e's static (fmt, out_fmt, qgamma, qcoef0, degree,
    dec_shift, chain) tuple.  Slot e is bit-identical to model e's own
    :func:`fxp_svm_model` call.
    """
    _tick()
    params = tuple(tuple(p) for p in params)
    if impl in ("xla", "ref"):
        return ref_ops.fxp_svm_fleet_ref(qx, sv, dual, icept, kind, params)
    e, m, n_feat = qx.shape
    n_sv, n_cls = dual.shape[1:]
    bits = params[0][0].total_bits
    uniform = len(set(params)) == 1
    if be is None or bm is None:
        tbe, tbm = tune.fleet_blocks(
            f"svm-{kind}", e, m, (n_feat, n_sv, n_cls), bits,
            uniform=uniform,
            vmem_bytes=lambda eb, b: fxp_model.svm_fleet_vmem_bytes(
                eb, n_sv, n_feat, n_cls, bits, b),
            budget=fxp_model.vmem_budget())
        be = tbe if be is None else be
        bm = tbm if bm is None else bm
    if not uniform:
        be = 1
    xp, m0 = _pad_axis(qx, 1, bm)
    rem = (-e) % be
    if rem:
        xp, _ = _pad_axis(xp, 0, be)
        sv, _ = _pad_axis(sv, 0, be)
        dual, _ = _pad_axis(dual, 0, be)
        icept, _ = _pad_axis(icept, 0, be)
        params = params + (params[0],) * rem
    chip = tune.on_tpu()
    xp, svp, dp, ip = _padded_fleet_svm_operands(xp, sv, dual, icept, chip)
    out = fxp_svm_fleet_pallas(xp, svp, dp, ip, kind, params, be=be, bm=bm,
                               interpret=not chip)
    return out[:e, :m0, :n_cls]


def _padded_fleet_svm_operands(qx, sv, dual, icept, chip: bool):
    """Lane-tile the SVM fleet operands' trailing dims for the chip (no-op
    for interpret mode); the model axis is never tiled."""
    if not chip:
        return qx, sv, dual, icept
    xp, _ = _pad_axis(qx, 2, _LANE)
    svp, _ = _pad_axis(sv, 1, _LANE)
    svp, _ = _pad_axis(svp, 2, _LANE)
    dp, _ = _pad_axis(dual, 1, _LANE)
    dp, _ = _pad_axis(dp, 2, _LANE)
    ip, _ = _pad_axis(icept, 1, _LANE)
    return xp, svp, dp, ip


def pwl_activation(x: jax.Array, variant: str = "pwl4",
                   impl: str = "pallas") -> jax.Array:
    """Fused PWL sigmoid/silu over any-shaped input.

    The block shape follows the actual input size (a batch-1 MLP call pads
    to at most one 128-lane row), instead of the historical fixed 256x512
    grid that padded every input to 131k elements.
    """
    _tick()
    if impl in ("xla", "ref"):
        return ref_ops.pwl_activation_ref(x, variant)
    orig_shape = x.shape
    flat = x.reshape(-1)
    block_rows, cols = tune.pwl_blocks(flat.shape[0])
    flat, n0 = _pad_axis(flat, 0, block_rows * cols)
    x2 = flat.reshape(-1, cols)
    out = pwl_activation_pallas(x2, variant, block_rows=block_rows,
                                block_cols=cols, interpret=not tune.on_tpu())
    return out.reshape(-1)[:n0].reshape(orig_shape)


# Packed-kernel operand cache: id-keyed weak entries instead of the old
# ``object.__setattr__(tree, "_packed_kernel", ...)`` mutation of user-owned
# model objects.  The weakref keeps identity honest across id() reuse and
# evicts the entry when the tree is collected.
_PACKED_TREES: Dict[int, Tuple[weakref.ref, dict]] = {}


def _packed_operands(tree: TreeArrays) -> tuple:
    key = id(tree)
    hit = _PACKED_TREES.get(key)
    if hit is not None and hit[0]() is tree:
        entry = hit[1]
    else:
        # numpy first: the first call may happen inside a jit/shard_map
        # trace, and a jnp constant created there is a tracer — caching it
        # leaks the trace and poisons every later call (seen as
        # UnexpectedTracerError when a mesh-specialized artifact traced the
        # tree kernel first).
        entry = {"np": tuple(np.asarray(t) for t in pack_tree(tree))}
        try:
            ref = weakref.ref(tree,
                              lambda _, k=key: _PACKED_TREES.pop(k, None))
            _PACKED_TREES[key] = (ref, entry)
        except TypeError:  # unexpected weakref-less tree type: don't cache
            pass
    # Memoize device-resident copies once we are outside any trace (a
    # concrete device array is a legal jit constant, so later traced calls
    # reuse it too); the eager serving hot path then never re-uploads the
    # packed operands per dispatch.  Inside a trace the copies come back
    # as tracers, which are never kept.
    if "dev" not in entry:
        dev = tuple(jnp.asarray(t) for t in entry["np"])
        if not any(isinstance(d, jax.core.Tracer) for d in dev):
            entry["dev"] = dev
    return entry.get("dev", entry["np"])


def tree_predict(tree: TreeArrays, x: jax.Array, impl: str = "pallas",
                 block_batch: int = 256) -> jax.Array:
    """Oblivious-tree inference.  x: (B, F) float -> (B,) int32."""
    _tick()
    if impl in ("xla", "ref"):
        return ref_ops.tree_ensemble_ref(tree, x)
    packed = _packed_operands(tree)
    # The block shrinks with the batch so tiny calls stay on one grid step,
    # but only to the batch's pow2 *bucket* (the serve/batching.py ladder),
    # and ragged batches are padded up to the bucket *here* — the jitted
    # kernel only ever sees bucket-shaped inputs, so a warm bucket hits the
    # jit cache instead of recompiling per distinct B.
    bb = tune.batch_bucket(x.shape[0], cap=block_batch)
    (f, n), l = packed[0].shape, packed[2].shape[1]
    if tree_vmem_bytes(f, n, l, bb) > tune._VMEM_BUDGET:
        raise ValueError(
            f"tree of {n} nodes x {l} leaves over {f} features needs "
            f"{tree_vmem_bytes(f, n, l, bb)} B of VMEM per step, over the "
            f"{tune._VMEM_BUDGET} B budget; serve it on 'xla'")
    xp, b0 = _pad_axis(jnp.asarray(x, jnp.float32), 0, bb)
    out = tree_ensemble_pallas(xp, *packed, block_batch=bb,
                               interpret=not tune.on_tpu())
    return out[:b0]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, impl: str = "pallas",
                    bq: int = 512, bk: int = 512) -> jax.Array:
    """(BH, S, dh) attention; S must be a multiple of the block size."""
    _tick()
    if impl in ("xla", "ref"):
        return ref_ops.flash_attention_ref(q, k, v, causal)
    return flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk,
                                  interpret=not tune.on_tpu())
