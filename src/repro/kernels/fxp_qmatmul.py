"""Pallas TPU kernel: fixed-point Qn.m matmul (paper C1 on the MXU).

Computes ``saturate(round_shift(A_int @ B_int, m))`` — the exact MCU
fixed-point matmul semantics — with MXU-friendly tiling:

* grid = (M/bm, N/bn, K/bk); K is the innermost (sequential) axis so each
  (i, j) output tile accumulates into a VMEM int32 scratch across K steps.
* A/B tiles are staged HBM->VMEM by ``BlockSpec``; the operands feed the
  MXU's int8 x int8 -> int32 path through ``mosaic.mxu_dot`` (int16 operands
  as 7-bit limbs), the final rounded shift and saturation run on the VPU at
  the last K step.
* block sizes default to 128/256 multiples (MXU alignment).

The pure-jnp oracle is :func:`repro.kernels.ref.fxp_qmatmul_ref`; tests sweep
shapes/dtypes in interpret mode against it.

Accumulator contract: the MXU accumulates int32.  The kernel is bit-exact
with the (int64-accumulating) oracle whenever the true dot-product magnitude
stays below 2^31 — always true for int8 inputs with K < 133k, and true for
int16/int32 inputs in the realistic quantized-NN value range (|values| a few
units, i.e. |q| << qmax).  Inputs saturating the container near qmax over
long K can wrap the accumulator — same failure mode as libfixmath's 32-bit
accumulate on MCUs; callers needing full-range int16 sums should use the
xla reference path (ops.fxp_qmatmul(impl='xla')).

32-bit containers have no exact MXU form (their int64 intermediates do not
exist in Mosaic): they run in interpret mode only, and the lowerings refuse
them on a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixedpoint
from repro.core.fixedpoint import FxpFormat

from .mosaic import mxu_dot, vpu_format

__all__ = ["fxp_qmatmul_pallas"]

def _kernel(a_ref, b_ref, o_ref, acc_ref, *, fmt: FxpFormat, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(a_ref[...], b_ref[...], (((1,), (0,)), ((), ())))

    @pl.when(k == k_steps - 1)
    def _finish():
        # The shared accumulator epilogue (one definition of the rounding
        # rule across kernels and oracles), traced onto the VPU.
        o_ref[...] = fixedpoint.rshift_round_saturate(acc_ref[...],
                                                      vpu_format(fmt))


@functools.partial(jax.jit, static_argnames=("fmt", "bm", "bn", "bk",
                                             "interpret"))
def fxp_qmatmul_pallas(a: jax.Array, b: jax.Array, fmt: FxpFormat,
                       bm: int = 128, bn: int = 128, bk: int = 256,
                       interpret: bool = False) -> jax.Array:
    """a: (M, K) intN, b: (K, N) intN -> (M, N) intN in the same Qn.m format.

    M, N, K must be divisible by the block sizes (the jit wrapper in ops.py
    pads).  ``interpret=True`` runs the kernel body on CPU for validation.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, bm, bn, bk)
    k_steps = k // bk

    kernel = functools.partial(_kernel, fmt=fmt, k_steps=k_steps)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), fmt.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a, b)
