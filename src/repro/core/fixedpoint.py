"""Qn.m fixed-point arithmetic library (paper §III-C, contribution C1).

Implements the EmbML fixed-point semantics in JAX: signed Qn.m numbers stored
in 8/16/32-bit integers (1 sign bit + ``n`` integer bits + ``m`` fractional
bits), saturating arithmetic, round-to-nearest rescaling, and the transcendental
helpers the paper's classifiers need (exp, sigmoid, tanh, sqrt, reciprocal,
power) — mirroring the fixedptc / libfixmath / AVRfix lineage the paper builds
on, but vectorized so the same semantics run on the TPU's integer datapath.

The paper's two experimental formats are provided as constants:

* ``FXP32`` — Q22.10 in an int32 container (22 might be wrong: paper says
  Q22.10, i.e. n=22 integer bits incl. none for sign? EmbML's convention is
  1 sign + 21 int + 10 frac = 32; we follow total=32, m=10).
* ``FXP16`` — Q12.4 in an int16 container (total=16, m=4).

Beyond-paper formats (``FXP8``, per-channel scaling) live in
:mod:`repro.core.quantize`; this module is the faithful global-format core.

Overflow/underflow accounting: the paper (§V-A) explains FXP16 accuracy cliffs
by the rate of overflow (saturation) and underflow (non-zero real rounded to
exactly zero). Every op here has an ``*_with_stats`` variant returning those
counts so the benchmark harness can reproduce that analysis.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FxpFormat",
    "FXP32",
    "FXP16",
    "FXP8",
    "STATS_DTYPE",
    "quantize",
    "dequantize",
    "qadd",
    "qsub",
    "qneg",
    "qmul",
    "qdiv",
    "qmatmul",
    "qmatmul_with_stats",
    "requantize",
    "rshift_round_saturate",
    "quantize_with_stats",
    "qexp",
    "qsigmoid",
    "qtanh",
    "qsqrt",
    "qrecip",
    "qpow_int",
    "qrelu",
    "FxpStats",
    "one_q",
    "exp_poly_consts",
]


@dataclasses.dataclass(frozen=True)
class FxpFormat:
    """A signed Qn.m fixed-point format in a ``total_bits`` integer container.

    value = stored_int / 2**frac_bits.  ``int_bits = total_bits - 1 - frac_bits``
    (one sign bit).  Representable range: [-(2**(total-1)) / 2**m,
    (2**(total-1) - 1) / 2**m].
    """

    total_bits: int
    frac_bits: int
    name: str = ""
    # Width of the wide intermediates; 0 = twice the container (the MCU's
    # wide type).  Kernel bodies widen 8-bit containers to 32 (see
    # repro.kernels.mosaic.vpu_format).
    wide_bits: int = 0

    def __post_init__(self):
        if self.total_bits not in (8, 16, 32):
            raise ValueError(f"unsupported container width {self.total_bits}")
        if not 0 <= self.frac_bits < self.total_bits:
            raise ValueError(f"frac_bits {self.frac_bits} out of range")
        if self.wide_bits not in (0, 16, 32, 64):
            raise ValueError(f"unsupported wide width {self.wide_bits}")

    # --- static properties -------------------------------------------------
    @property
    def int_bits(self) -> int:
        return self.total_bits - 1 - self.frac_bits

    @property
    def scale(self) -> float:
        return float(2 ** self.frac_bits)

    @property
    def qmin(self) -> int:
        return -(2 ** (self.total_bits - 1))

    @property
    def qmax(self) -> int:
        return 2 ** (self.total_bits - 1) - 1

    @property
    def min_value(self) -> float:
        return self.qmin / self.scale

    @property
    def max_value(self) -> float:
        return self.qmax / self.scale

    @property
    def resolution(self) -> float:
        return 1.0 / self.scale

    @property
    def dtype(self) -> jnp.dtype:
        return {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[self.total_bits]

    @property
    def wide_dtype(self) -> jnp.dtype:
        """Accumulator dtype wide enough to hold a product of two values."""
        bits = self.wide_bits or 2 * self.total_bits
        return {16: jnp.int16, 32: jnp.int32, 64: jnp.int64}[bits]

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return self.name or f"Q{self.int_bits}.{self.frac_bits}/{self.total_bits}b"


# The paper's experimental formats (§IV): FXP32 = Q22.10, FXP16 = Q12.4.
FXP32 = FxpFormat(32, 10, "FXP32(Q22.10)")
FXP16 = FxpFormat(16, 4, "FXP16(Q12.4)")
# Beyond-paper: 8-bit container (Q5.2 default) for MXU int8 paths.
FXP8 = FxpFormat(8, 2, "FXP8(Q5.2)")


# Counter dtype for in-program overflow/underflow accounting.  Explicitly
# int32: the old ``jnp.int64`` spelling silently downgraded to int32 whenever
# jax x64 was disabled (the default), so it was an int32 counter wearing a
# wide label — and worse, flipped width under ``jax.config.update``.  One
# predict call cannot overflow int32 (it would need > 2^31 observed elements
# in a single batch); cross-call accumulation happens on the host through
# :meth:`FxpStats.merge`, which promotes concrete counters to numpy int64 so
# long serving runs never wrap.
STATS_DTYPE = jnp.int32


def _is_concrete(x) -> bool:
    """True when ``x`` is a host value (numpy / python / committed array),
    i.e. not an abstract tracer inside a jit/shard_map trace."""
    return not isinstance(x, jax.core.Tracer)


@dataclasses.dataclass
class FxpStats:
    """Overflow/underflow accounting (paper §V-A)."""

    overflow: jax.Array  # count of saturated elements
    underflow: jax.Array  # count of non-zero reals rounded to exactly zero
    total: jax.Array  # number of elements observed

    def merge(self, other: "FxpStats") -> "FxpStats":
        def add(a, b):
            # Host-side accumulation promotes to int64: the in-program
            # counters are deliberately int32 (see STATS_DTYPE), which is
            # safe per call but would wrap when a long serving run keeps
            # merging per-request stats into one running total.  Inside a
            # trace the operands are tracers and stay on the program dtype.
            if _is_concrete(a) and _is_concrete(b):
                return np.asarray(a, np.int64) + np.asarray(b, np.int64)
            return a + b

        return FxpStats(
            add(self.overflow, other.overflow),
            add(self.underflow, other.underflow),
            add(self.total, other.total),
        )


# Pytree registration lets jitted predict programs return FxpStats directly
# (the compile pipeline jits artifacts for the xla/pallas backends).
jax.tree_util.register_pytree_node(
    FxpStats,
    lambda s: ((s.overflow, s.underflow, s.total), None),
    lambda _, children: FxpStats(*children),
)


def _saturate(x_wide: jax.Array, fmt: FxpFormat) -> jax.Array:
    # Bounds typed to the operand: Python-int bounds are int64 under x64,
    # which Mosaic cannot lower inside a kernel body.
    lo = jnp.asarray(fmt.qmin, x_wide.dtype)
    hi = jnp.asarray(fmt.qmax, x_wide.dtype)
    return jnp.minimum(jnp.maximum(x_wide, lo), hi).astype(fmt.dtype)


def one_q(fmt: FxpFormat) -> int:
    """The constant 1.0 quantized into ``fmt``, saturating.

    For formats with at least one integer bit this is exactly ``1 << m``.
    Formats with zero integer bits (``m == total_bits - 1``, e.g. Q0.31)
    cannot represent 1.0; the saturated value ``qmax`` is the closest
    representable number.  Materializing the raw ``1 << m`` as a container
    constant raises ``OverflowError`` on those formats, which is what every
    sigmoid/recip path used to do.
    """
    return min(1 << fmt.frac_bits, fmt.qmax)


def exp_poly_consts(fmt: FxpFormat) -> Tuple[int, Tuple[int, int, int, int]]:
    """Per-format integer constants of :func:`qexp`: ``(log2e_q, (c0..c3))``.

    Shared between the traced implementation below and the C emitter
    (:mod:`repro.emit`), so both quantize the polynomial identically.
    """
    log2e_q = int(round(_LOG2_E * fmt.scale))
    coeffs = tuple(int(round(c * fmt.scale)) for c in _EXP2_COEFFS)
    return log2e_q, coeffs


# --------------------------------------------------------------------------
# Conversion
# --------------------------------------------------------------------------
def quantize(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """float -> Qn.m integer, round-to-nearest-even, saturating."""
    scaled = jnp.asarray(x, jnp.float32) * fmt.scale
    q = jnp.round(scaled)
    q = jnp.clip(q, fmt.qmin, fmt.qmax)
    return q.astype(fmt.dtype)


def quantize_with_stats(x: jax.Array, fmt: FxpFormat) -> Tuple[jax.Array, FxpStats]:
    scaled = jnp.asarray(x, jnp.float32) * fmt.scale
    q = jnp.round(scaled)
    over = jnp.sum((q > fmt.qmax) | (q < fmt.qmin), dtype=STATS_DTYPE)
    under = jnp.sum((q == 0) & (x != 0), dtype=STATS_DTYPE)
    q = jnp.clip(q, fmt.qmin, fmt.qmax).astype(fmt.dtype)
    return q, FxpStats(over, under, jnp.asarray(x.size, STATS_DTYPE))


def dequantize(q: jax.Array, fmt: FxpFormat) -> jax.Array:
    return q.astype(jnp.float32) / fmt.scale


# --------------------------------------------------------------------------
# Basic saturating arithmetic
# --------------------------------------------------------------------------
def qadd(a: jax.Array, b: jax.Array, fmt: FxpFormat) -> jax.Array:
    wide = a.astype(fmt.wide_dtype) + b.astype(fmt.wide_dtype)
    return _saturate(wide, fmt)


def qsub(a: jax.Array, b: jax.Array, fmt: FxpFormat) -> jax.Array:
    wide = a.astype(fmt.wide_dtype) - b.astype(fmt.wide_dtype)
    return _saturate(wide, fmt)


def qneg(a: jax.Array, fmt: FxpFormat) -> jax.Array:
    return _saturate(-a.astype(fmt.wide_dtype), fmt)


def _rshift_round(x_wide: jax.Array, m: int) -> jax.Array:
    """Arithmetic right shift by ``m`` with round-to-nearest (ties away from 0).

    Matches the MCU semantics ``(x + (1 << (m-1))) >> m`` for positive x and
    its symmetric form for negative x, implemented branch-free.  Computed via
    floor-shift + remainder so no intermediate (``abs(x)`` or ``x + half``)
    can overflow the container: the result is exact for every representable
    ``x`` including the dtype's min/max, which the fused-kernel epilogue
    relies on when the int32 accumulator sits at a saturation boundary.
    """
    if m == 0:
        return x_wide
    half = jnp.asarray(1, x_wide.dtype) << (m - 1)
    floor_q = x_wide >> m  # floor(x / 2^m): arithmetic shift
    rem = x_wide - (floor_q << m)  # remainder in [0, 2^m)
    # Ties round away from zero: for x >= 0 bump on rem >= half, for x < 0
    # (where floor sits one below the truncated quotient) on rem > half.
    # Compared as rem > half - (x >= 0): rem itself can be the dtype max
    # (x = max, m = width - 1), so nothing may be added to it.
    bump = rem > (half - (x_wide >= 0))
    return floor_q + bump.astype(x_wide.dtype)


def requantize(acc: jax.Array, shift: int, fmt: FxpFormat) -> jax.Array:
    """``saturate(round_shift(acc, shift))`` — the mixed-format epilogue.

    A product of a Q·.ma value and a Q·.mb value accumulates at scale
    ``2^(ma+mb)``; ``shift = ma + mb - m_out`` re-scales it into the output
    format.  With one global format this degenerates to
    ``shift == fmt.frac_bits`` (see :func:`rshift_round_saturate`); with a
    calibrated per-tensor :class:`repro.quant.QuantPlan` every layer passes
    its own shift.  ``shift`` must be non-negative (the planner guarantees
    ``m_out <= ma + mb``).
    """
    if shift < 0:
        raise ValueError(f"requantize shift must be >= 0, got {shift}")
    return _saturate(_rshift_round(acc, shift), fmt)


def rshift_round_saturate(acc: jax.Array, fmt: FxpFormat) -> jax.Array:
    """``saturate(round_shift(acc, m))`` — the shared accumulator epilogue.

    Pure jnp, so it traces both into jitted reference programs and into the
    Pallas kernel bodies (fxp_qmatmul / fxp_layer) — one definition of the
    rounding rule keeps the cross-backend bit-identity contract in one place.
    """
    return requantize(acc, fmt.frac_bits, fmt)


def qmul(a: jax.Array, b: jax.Array, fmt: FxpFormat) -> jax.Array:
    """(a*b) >> m with rounding, saturating — elementwise Qn.m multiply."""
    wide = a.astype(fmt.wide_dtype) * b.astype(fmt.wide_dtype)
    return _saturate(_rshift_round(wide, fmt.frac_bits), fmt)


def qdiv(a: jax.Array, b: jax.Array, fmt: FxpFormat) -> jax.Array:
    """(a << m) / b with round-to-nearest, saturating. b == 0 saturates."""
    wide = fmt.wide_dtype
    wide_a = a.astype(wide) << fmt.frac_bits
    wide_b = b.astype(wide)
    # Every constant typed to the wide dtype: a Python scalar in jnp.where
    # is int64 under x64, which no kernel body can hold.
    one = jnp.ones_like(wide_b)
    safe_b = jnp.where(wide_b == 0, one, wide_b)
    sign = jnp.where((wide_a < 0) != (safe_b < 0), -one, one)
    # C-style truncating division on magnitudes, then round-to-nearest
    # (ties away from zero) — matches the MCU fixed-point division macro.
    q_trunc = sign * (jnp.abs(wide_a) // jnp.abs(safe_b))
    rem_t = wide_a - q_trunc * safe_b
    adjust_t = (jnp.abs(rem_t) * 2 >= jnp.abs(safe_b)).astype(wide)
    q_rounded = q_trunc + adjust_t * sign
    overflow = jnp.where(wide_a >= 0, jnp.asarray(fmt.qmax, wide),
                         jnp.asarray(fmt.qmin, wide))
    out = jnp.where(wide_b == 0, overflow, q_rounded)
    return _saturate(out, fmt)


def qrelu(a: jax.Array, fmt: FxpFormat) -> jax.Array:
    del fmt
    return jnp.maximum(a, 0)


# --------------------------------------------------------------------------
# Matrix multiply — the inference hot spot
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("fmt", "preferred_wide"))
def qmatmul(a: jax.Array, b: jax.Array, fmt: FxpFormat, preferred_wide: bool = True) -> jax.Array:
    """Fixed-point matmul: wide-accumulate int products, then one rounded
    right-shift by ``m`` and saturation (MCU semantics; maps to MXU int paths).

    a: (..., K) int, b: (K, N) int -> (..., N) int in the same format.
    """
    wide = fmt.wide_dtype if preferred_wide else jnp.int32
    acc = jax.lax.dot_general(
        a.astype(wide),
        b.astype(wide),
        (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=wide,
    )
    return _saturate(_rshift_round(acc, fmt.frac_bits), fmt)


def qmatmul_with_stats(a: jax.Array, b: jax.Array, fmt: FxpFormat,
                       shift: Optional[int] = None) -> Tuple[jax.Array, FxpStats]:
    """Like :func:`qmatmul` but also returns overflow/underflow counts.

    ``shift`` overrides the requantization amount for mixed-format operands
    (``ma + mb - m_out``); ``None`` keeps the single-format semantics
    (shift by ``fmt.frac_bits``).
    """
    shift = fmt.frac_bits if shift is None else shift
    wide = fmt.wide_dtype
    acc = jax.lax.dot_general(
        a.astype(wide),
        b.astype(wide),
        (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=wide,
    )
    shifted = _rshift_round(acc, shift)
    over = jnp.sum((shifted > fmt.qmax) | (shifted < fmt.qmin),
                   dtype=STATS_DTYPE)
    under = jnp.sum((shifted == 0) & (acc != 0), dtype=STATS_DTYPE)
    out = _saturate(shifted, fmt)
    total = jnp.asarray(out.size, STATS_DTYPE)
    return out, FxpStats(over, under, total)


# --------------------------------------------------------------------------
# Transcendentals (range-reduced polynomials, pure integer ops)
# --------------------------------------------------------------------------
# 2^f for f in [0,1) as a cubic minimax polynomial; coefficients in float,
# quantized per-format at trace time.  max |err| ~ 1e-4 over [0,1).
_EXP2_COEFFS = (0.9999936, 0.6964313, 0.2243984, 0.0792043)
_LOG2_E = 1.4426950408889634


def qexp(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """Fixed-point exp(x): exp(x) = 2^(x*log2e) = 2^k * 2^f, f in [0,1).

    Implemented entirely in Qn.m integer ops (one widening multiply per
    polynomial term), mirroring libfixmath's exp.  Saturates on overflow,
    flushes to zero for k below -m (true underflow, which the paper counts).
    """
    m = fmt.frac_bits
    wide = fmt.wide_dtype
    log2e_q, (c0, c1, c2, c3) = exp_poly_consts(fmt)
    y = _rshift_round(x.astype(wide) * log2e_q, m)  # y = x*log2e in Qn.m (wide)
    k = y >> m  # floor(y): arithmetic shift == floor for two's complement
    f = y - (k << m)  # fractional part in [0, 2^m)
    # Horner in Qn.m on the wide dtype.
    acc = jnp.full_like(f, c3)
    acc = _rshift_round(acc * f, m) + c2
    acc = _rshift_round(acc * f, m) + c1
    acc = _rshift_round(acc * f, m) + c0  # ~2^f in Qn.m, in [2^m, 2^(m+1))
    # Scale by 2^k: left shift when k>=0 (with saturation), right when k<0.
    k_i32 = k.astype(jnp.int32)
    max_shift = fmt.total_bits  # beyond this always saturates / flushes
    k_clamped = jnp.minimum(jnp.maximum(k_i32, jnp.int32(-max_shift)),
                            jnp.int32(max_shift))
    zero = jnp.zeros_like(k_clamped)
    pos = jnp.maximum(k_clamped, zero).astype(wide)
    neg = jnp.maximum(-k_clamped, zero).astype(wide)
    shifted_up = acc << jnp.minimum(pos, fmt.total_bits - 1).astype(wide)
    # Detect overflow of the left shift on the wide dtype.
    overflowed = (shifted_up >> jnp.minimum(pos, fmt.total_bits - 1).astype(wide)) != acc
    up = jnp.where(overflowed, jnp.asarray(fmt.qmax, wide), shifted_up)
    down = _rshift_round(acc, 0) >> jnp.minimum(neg, fmt.total_bits + m).astype(wide)
    out = jnp.where(k_clamped >= 0, up, down)
    # Saturate positive overflow (k too large).
    out = jnp.where(k_i32 >= fmt.int_bits, jnp.asarray(fmt.qmax, wide), out)
    return _saturate(out, fmt)


def qrecip(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """1/x in Qn.m via exact integer division (2^(2m) / q)."""
    one = jnp.asarray(one_q(fmt), fmt.dtype)
    return qdiv(jnp.broadcast_to(one, x.shape), x, fmt)


def qsigmoid(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """Exact-form fixed-point sigmoid: 1/(1+exp(-x)) in Qn.m.

    Uses exp(-|x|) (always in (0,1], no overflow) and the identity
    sigmoid(x) = 1 - sigmoid(-x) for the negative branch.
    """
    neg_abs = -jnp.abs(x.astype(fmt.wide_dtype))
    e = qexp(_saturate(neg_abs, fmt), fmt)  # exp(-|x|) in (0, 1]
    one = jnp.asarray(one_q(fmt), fmt.dtype)
    denom = qadd(jnp.broadcast_to(one, e.shape), e, fmt)
    pos = qdiv(jnp.broadcast_to(one, e.shape), denom, fmt)  # sigmoid(|x|)
    neg = qsub(jnp.broadcast_to(one, e.shape), pos, fmt)
    return jnp.where(x.astype(fmt.wide_dtype) >= 0, pos, neg)


def qtanh(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """tanh(x) = 2*sigmoid(2x) - 1, all in Qn.m."""
    two_x = _saturate(x.astype(fmt.wide_dtype) << 1, fmt)
    s = qsigmoid(two_x, fmt)
    wide = s.astype(fmt.wide_dtype) * 2 - int(fmt.scale)
    return _saturate(wide, fmt)


def qsqrt(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """sqrt in Qn.m via integer Newton iterations on 2^m * sqrt(v).

    sqrt(q / 2^m) * 2^m = sqrt(q * 2^m); compute isqrt of (q << m) on the wide
    dtype with enough Newton steps for the container width.
    """
    wide = fmt.wide_dtype
    v = jnp.maximum(x.astype(wide), 0) << fmt.frac_bits
    # Initial guess: 2^(ceil(bits/2)) scale — use float rsqrt seed for speed,
    # then integer-Newton to exactness.
    seed = jnp.sqrt(jnp.maximum(v.astype(jnp.float32), 1.0)).astype(wide)
    guess = jnp.maximum(seed, 1)

    def newton(g, _):
        g = (g + v // jnp.maximum(g, 1)) >> 1
        return g, None

    guess, _ = jax.lax.scan(newton, guess, None, length=4)
    guess = jnp.where(v == 0, 0, guess)
    return _saturate(guess, fmt)


def qpow_int(x: jax.Array, p: int, fmt: FxpFormat) -> jax.Array:
    """x**p for small non-negative integer p (poly-kernel SVM degree)."""
    if p < 0:
        raise ValueError("qpow_int only supports non-negative integer powers")
    out = jnp.full_like(x, one_q(fmt))  # 1.0 in Qn.m (saturated if n == 0)
    base = x
    while p:
        if p & 1:
            out = qmul(out, base, fmt)
        base = qmul(base, base, fmt)
        p >>= 1
    return out
