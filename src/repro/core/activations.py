"""Sigmoid approximations for MLP inference (paper §III-D, contribution C3).

The paper offers three drop-in replacements for the sigmoid at *inference*
time (training always uses the true sigmoid):

* ``rational`` — ``0.5 + 0.5*x / (1 + |x|)``
* ``pwl2``     — 2-point piecewise-linear: one ramp ``0.25x + 0.5`` clamped to
  [0, 1] (breakpoints at x = ±2).
* ``pwl4``     — 4-point piecewise-linear (the classic PLAN approximation,
  Amin et al. 1997, which EmbML's curve in Fig. 2 matches): per-|x| segments
  with slopes {0.25, 0.125, 0.03125} and saturation at |x| ≥ 5.

All PWL slopes are exact negative powers of two, so the fixed-point versions
are pure shift/add — the property that makes them fast on FPU-less MCUs *and*
on the TPU VPU (no transcendental, just select/fma).  Each approximation is
provided in the float domain and in the Qn.m integer domain.

Registry entries are keyed by the names used throughout configs/benchmarks:
``exact | rational | pwl2 | pwl4``.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

from .fixedpoint import FxpFormat, _rshift_round, _saturate, one_q, qdiv, qsigmoid

__all__ = [
    "sigmoid_exact",
    "sigmoid_rational",
    "sigmoid_pwl2",
    "sigmoid_pwl4",
    "get_sigmoid",
    "get_qsigmoid",
    "pwl4_consts",
    "SIGMOID_MAX_ERR",
    "SIGMOID_NAMES",
]

SIGMOID_NAMES = ("exact", "rational", "pwl2", "pwl4")

# Measured sup-norm error of each approximation vs the true sigmoid (float
# domain); used as test bounds.  rational's sup error is ~0.0823 (attained as
# |x|→∞ tail gap); pwl2 peaks near the ±2 breakpoint (~0.119); pwl4/PLAN ≤ 0.019.
SIGMOID_MAX_ERR = {"exact": 0.0, "rational": 0.0830, "pwl2": 0.1200, "pwl4": 0.0200}


# --------------------------------------------------------------------------
# Float domain
# --------------------------------------------------------------------------
def sigmoid_exact(x: jax.Array) -> jax.Array:
    return jax.nn.sigmoid(x)


def sigmoid_rational(x: jax.Array) -> jax.Array:
    """0.5 + 0.5*x/(1+|x|) — smooth, one divide, no exp."""
    return 0.5 + 0.5 * x / (1.0 + jnp.abs(x))


def sigmoid_pwl2(x: jax.Array) -> jax.Array:
    """Single ramp clamped to [0,1]; breakpoints ±2."""
    return jnp.clip(0.25 * x + 0.5, 0.0, 1.0)


def sigmoid_pwl4(x: jax.Array) -> jax.Array:
    """PLAN 4-segment PWL (per half-axis), symmetric via 1 - f(|x|)."""
    ax = jnp.abs(x)
    y = jnp.where(
        ax >= 5.0,
        1.0,
        jnp.where(
            ax >= 2.375,
            0.03125 * ax + 0.84375,
            jnp.where(ax >= 1.0, 0.125 * ax + 0.625, 0.25 * ax + 0.5),
        ),
    )
    return jnp.where(x >= 0, y, 1.0 - y)


_FLOAT_REGISTRY: Dict[str, Callable[[jax.Array], jax.Array]] = {
    "exact": sigmoid_exact,
    "rational": sigmoid_rational,
    "pwl2": sigmoid_pwl2,
    "pwl4": sigmoid_pwl4,
}


def get_sigmoid(name: str) -> Callable[[jax.Array], jax.Array]:
    try:
        return _FLOAT_REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown sigmoid '{name}', expected one of {SIGMOID_NAMES}")


# --------------------------------------------------------------------------
# Qn.m integer domain — slopes are power-of-two shifts
# --------------------------------------------------------------------------
def qsigmoid_rational(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """0.5 + 0.5*x/(1+|x|) in Qn.m: one integer divide, one shift."""
    one = int(fmt.scale)
    half = one >> 1
    ax = jnp.abs(x.astype(fmt.wide_dtype))
    denom = _saturate(ax + one, fmt)
    ratio = qdiv(x, denom, fmt)  # x / (1+|x|) in (-1, 1)
    out = half + _rshift_round(ratio.astype(fmt.wide_dtype), 1)
    return _saturate(out, fmt)


def qsigmoid_pwl2(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """clip(x>>2 + 0.5, 0, 1) in Qn.m — two shifts, one clamp.

    The upper clamp is ``min(1.0, qmax)``: for formats with no integer bits
    (m == total_bits - 1) the raw ``1 << m`` exceeds the container, and the
    old ``astype`` narrowing wrapped it to ``qmin`` — sigmoid(large x) came
    out as the most negative representable value.  Saturate instead.
    """
    one = one_q(fmt)
    half = int(fmt.scale) >> 1
    ramp = _rshift_round(x.astype(fmt.wide_dtype), 2) + half
    ramp = jnp.minimum(jnp.maximum(ramp, jnp.zeros_like(ramp)),
                       jnp.asarray(one, ramp.dtype))
    return _saturate(ramp, fmt)


def pwl4_consts(fmt: FxpFormat) -> Dict[str, int]:
    """Integer constants of the PLAN approximation for ``fmt``.

    One definition shared by the traced op below and the C emitter
    (:mod:`repro.emit`).  Thresholds are exact (wide-domain) values; the
    ``one`` used for the final ``1 - y`` reflection stays unsaturated so the
    symmetry identity holds before the final saturation.
    """
    one = int(fmt.scale)
    return {
        "one": one,
        "half": one >> 1,
        "t5": 5 * one,
        "t2375": int(round(2.375 * fmt.scale)),
        "t1": one,
        "c84375": int(round(0.84375 * fmt.scale)),
        "c625": int(round(0.625 * fmt.scale)),
    }


def qsigmoid_pwl4(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """PLAN segments in Qn.m.  Constants quantized once per format."""
    consts = pwl4_consts(fmt)
    one = consts["one"]
    wide = fmt.wide_dtype
    ax = jnp.abs(x.astype(wide))
    t5 = consts["t5"]
    t2375 = consts["t2375"]
    t1 = consts["t1"]
    c84375 = consts["c84375"]
    c625 = consts["c625"]
    half = consts["half"]
    y = jnp.where(
        ax >= t5,
        jnp.asarray(one, wide),
        jnp.where(
            ax >= t2375,
            _rshift_round(ax, 5) + c84375,
            jnp.where(ax >= t1, _rshift_round(ax, 3) + c625, _rshift_round(ax, 2) + half),
        ),
    )
    y = jnp.where(x.astype(wide) >= 0, y, one - y)
    return _saturate(y, fmt)


def qsigmoid_exact(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    return qsigmoid(x, fmt)


_FXP_REGISTRY = {
    "exact": qsigmoid_exact,
    "rational": qsigmoid_rational,
    "pwl2": qsigmoid_pwl2,
    "pwl4": qsigmoid_pwl4,
}


def get_qsigmoid(name: str) -> Callable[[jax.Array, FxpFormat], jax.Array]:
    try:
        return _FXP_REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown sigmoid '{name}', expected one of {SIGMOID_NAMES}")
