"""Pallas TPU kernel: fused fixed-point layer (matmul + bias + PWL, one pass).

The inference hot path of every fixed-point classifier is the layer
``act(qadd(saturate(round_shift(A @ B, m)), bias))`` — which the chained ops
executed as three dispatches (``fxp_qmatmul`` -> ``qadd`` -> ``qsigmoid``),
each round-tripping the activations through HBM with its own pad/unpad.
This kernel computes the whole layer in one ``pallas_call``:

* grid = (M/bm, N/bn, K/bk), K innermost (sequential), so each (i, j) output
  tile accumulates into a VMEM int32 scratch across the K steps — the
  accumulator never leaves VMEM;
* at the final K step the epilogue runs on the VPU over the tile still in
  VMEM: rounded shift by ``m``, saturation to the container, the bias add
  (re-widened, saturating), and the Qn.m integer-domain activation — the
  exact :mod:`repro.core.activations` ``qsigmoid_*`` functions, traced into
  the kernel body, so the fused path is *bit-identical* to the chained ops
  by construction;
* activations between matmul and nonlinearity never touch HBM.

Accumulator contract: identical to :mod:`.fxp_qmatmul` — int32 MXU
accumulation, bit-exact vs the wide-accumulating oracle whenever the true
dot-product magnitude stays below 2^31 (always for int8 with K < 133k; the
realistic quantized range for int16/int32).  Callers needing full-range
sums use the xla reference path.

The pure-jnp oracle is :func:`repro.kernels.ref.fxp_layer_ref`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixedpoint
from repro.core.activations import get_qsigmoid
from repro.core.fixedpoint import FxpFormat

from .mosaic import index_map, mxu_dot, vpu_format

__all__ = ["fxp_layer_pallas", "LAYER_ACTIVATIONS"]

# "none" = linear output layer (logits); the rest are Qn.m sigmoid variants.
LAYER_ACTIVATIONS = ("none", "exact", "rational", "pwl2", "pwl4")


def _kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *, fmt: FxpFormat,
            activation: str, shift: int, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(a_ref[...], b_ref[...], (((1,), (0,)), ((), ())))

    @pl.when(k == k_steps - 1)
    def _epilogue():
        # The epilogue traces the *same* fixedpoint/activation functions the
        # ref oracle composes — one definition of every rule, so the fused
        # path cannot drift from the chained semantics.  ``shift`` carries
        # mixed-format operands (per-tensor QuantPlan) into the output
        # format; for single-format layers it equals ``fmt.frac_bits``.
        vfmt = vpu_format(fmt)
        h = fixedpoint.requantize(acc_ref[...], shift, vfmt)
        h = fixedpoint.qadd(h, bias_ref[...], vfmt)
        if activation != "none":
            h = get_qsigmoid(activation)(h, vfmt)
        o_ref[...] = h.astype(fmt.dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "activation", "shift",
                                             "bm", "bn", "bk", "interpret"))
def fxp_layer_pallas(a: jax.Array, b: jax.Array, bias: jax.Array,
                     fmt: FxpFormat, activation: str = "none",
                     shift: Optional[int] = None, bm: int = 128,
                     bn: int = 128, bk: int = 256,
                     interpret: bool = False) -> jax.Array:
    """a: (M, K), b: (K, N), bias: (N,) intN -> act(a @ b + bias): (M, N) intN.

    M, N, K must be divisible by the block sizes (the ``ops.py`` wrapper pads
    to the tuned blocks).  ``shift`` is the requantization amount for
    mixed-format operands (None = ``fmt.frac_bits``, the single-format
    semantics).  ``interpret=True`` runs the body on CPU.
    """
    if activation not in LAYER_ACTIVATIONS:
        raise KeyError(f"activation must be one of {LAYER_ACTIVATIONS}")
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and bias.shape == (n,), (a.shape, b.shape, bias.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (a.shape, b.shape, bm, bn, bk)
    k_steps = k // bk

    kernel = functools.partial(
        _kernel, fmt=fmt, activation=activation,
        shift=fmt.frac_bits if shift is None else shift, k_steps=k_steps)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), index_map(lambda i, j, kk: (0, j))),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), fmt.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a, b, bias.reshape(1, n))
