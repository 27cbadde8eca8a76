"""What Mosaic, the TPU kernel compiler, takes — shared by every kernel.

Three limits of the chip shape the fixed-point kernel bodies:

* the MXU multiplies integers only as int8 x int8 -> int32
  (:func:`mxu_dot` builds every integer dot from that);
* the VPU has no 16-bit integer arithmetic, so an 8-bit container's wide
  intermediates run as int32 inside a kernel (:func:`vpu_format`);
* no 64-bit value exists in a kernel, and under ``jax_enable_x64`` a
  Python-int block index traces as int64 (:func:`index_map`).

Each helper keeps the exact integer semantics of the wide-accumulating
reference, so interpret mode (which has none of these limits) and the chip
compute the same bits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fixedpoint import FxpFormat

__all__ = ["mxu_dot", "vpu_format", "index_map"]

_LIMB = 7  # int16 -> three limbs: two unsigned 7-bit, one signed 2-bit


def _limbs(v: jax.Array):
    """int16 -> (lo, mid, hi) int8 limbs with v == hi<<14 + mid<<7 + lo."""
    w = v.astype(jnp.int32)
    mask = jnp.int32((1 << _LIMB) - 1)
    return ((w & mask).astype(jnp.int8),
            ((w >> jnp.int32(_LIMB)) & mask).astype(jnp.int8),
            (w >> jnp.int32(2 * _LIMB)).astype(jnp.int8))


def mxu_dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """Exact integer ``dot_general`` into a wrapping int32 accumulator, in
    the only integer form the TPU MXU takes: int8 x int8 -> int32.

    int8 operands go in as they are.  int16 operands are split into three
    int8 limbs each; the nine limb products are exact int32 dots, and
    recombining them with wrapping int32 shifts and adds gives the int32
    dot of the int16 values modulo 2^32 — the same bits, wraparound
    included, as widening both to int32 first.  int32 operands (32-bit
    containers) keep the plain int32 dot, which only interpret mode runs:
    Mosaic refuses it, and the lowerings refuse such Targets on a TPU.
    """
    if a.dtype != b.dtype or a.dtype not in (jnp.int8, jnp.int16, jnp.int32):
        raise TypeError(f"mxu_dot takes equal int8/int16/int32 operands, "
                        f"got {a.dtype} x {b.dtype}")
    if a.dtype != jnp.int16:
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.int32)
    al, bl = _limbs(a), _limbs(b)
    # Sum the limb products of equal weight 2^(7s), then fold the five
    # weights Horner-style from the top: every step wraps like int32.
    by_weight = [None] * 5
    for i, ai in enumerate(al):
        for j, bj in enumerate(bl):
            p = jax.lax.dot_general(ai, bj, dims,
                                    preferred_element_type=jnp.int32)
            s = i + j
            by_weight[s] = p if by_weight[s] is None else by_weight[s] + p
    acc = by_weight[4]
    for part in reversed(by_weight[:4]):
        acc = (acc << jnp.int32(_LIMB)) + part
    return acc


def vpu_format(fmt: FxpFormat) -> FxpFormat:
    """``fmt`` as a kernel body computes it: 8-bit containers widen to
    int32 instead of int16.

    Exact for every elementwise fixed-point op: no op on 8-bit values
    leaves the int16 range before it saturates (products and shifted
    operands stay below 2^15), so int32 holds the same values.  Sums over
    many terms are not elementwise: the reference widens those to int64,
    and the kernel that sums says how it matches that.
    """
    if fmt.total_bits != 8:
        return fmt
    return FxpFormat(fmt.total_bits, fmt.frac_bits, fmt.name, wide_bits=32)


def index_map(fn):
    """A BlockSpec index map whose Python-int block indices trace as int32
    (under ``jax_enable_x64`` they would be int64, which Mosaic refuses).
    numpy scalars, not jax Arrays: an index map may capture no constant,
    and the tuner traces kernels under ``ensure_compile_time_eval``."""
    def mapped(*ids):
        return tuple(np.int32(v) if isinstance(v, int) else v
                     for v in fn(*ids))
    return mapped
