"""The plain references that decide ``correct``, in numpy alone.

Nothing here imports the program or takes anything it made: the weights
are the benchmark's own float weights, drawn from the configuration.

* :func:`mlp_logits` — the float forward pass of a sigmoid MLP, in
  float64: ``sigmoid(x W0 + b0) ... W_last + b_last``.
* :func:`fixed_point_classes` — the same MLP in the fixed-point arithmetic
  the configuration states (one global signed Qn.m format, EmbML's FXP16 =
  Q12.4): inputs, weights and biases rounded to nearest (ties to even) and
  saturated; each layer an exact integer dot, a right shift by ``m`` that
  rounds ties away from zero, a saturating bias add, and on hidden layers
  the fixed-point sigmoid ``1 / (1 + exp(-|x|))`` (exp as ``2^k * p(f)``
  with a cubic ``p`` whose coefficients are rounded to the format, the
  division rounded to nearest), mirrored for negative inputs; the class
  is the first largest output.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

# exp(x) = 2^(x log2 e); 2^f on [0, 1) as a cubic (libfixmath's fit).
EXP2_COEFFS = (0.9999936, 0.6964313, 0.2243984, 0.0792043)
LOG2_E = 1.4426950408889634


def mlp_logits(x: np.ndarray, weights: Sequence[np.ndarray],
               biases: Sequence[np.ndarray]) -> np.ndarray:
    h = np.asarray(x, np.float64)
    for i, (w, b) in enumerate(zip(weights, biases)):
        h = h @ np.asarray(w, np.float64) + np.asarray(b, np.float64)
        if i < len(weights) - 1:
            h = 1.0 / (1.0 + np.exp(-h))
    return h


class Qfmt:
    """A signed Qn.m format in a ``bits``-wide container."""

    def __init__(self, bits: int, frac: int):
        self.bits, self.m = int(bits), int(frac)
        self.lo, self.hi = -(1 << (self.bits - 1)), (1 << (self.bits - 1)) - 1

    def sat(self, v):
        return np.clip(v, self.lo, self.hi)

    def quantize(self, x) -> np.ndarray:
        scaled = np.asarray(x, np.float32) * np.float32(2.0 ** self.m)
        return self.sat(np.round(scaled).astype(np.int64))


def _shift_round(v: np.ndarray, m: int) -> np.ndarray:
    """``v / 2^m`` rounded to nearest, ties away from zero."""
    if m == 0:
        return v
    half = 1 << (m - 1)
    fl = v >> m
    rem = v - (fl << m)
    return fl + (rem > (half - (v >= 0))).astype(np.int64)


def _qexp(x: np.ndarray, f: Qfmt) -> np.ndarray:
    m = f.m
    log2e = int(round(LOG2_E * 2.0 ** m))
    c0, c1, c2, c3 = (int(round(c * 2.0 ** m)) for c in EXP2_COEFFS)
    y = _shift_round(x * log2e, m)
    k = y >> m
    frac = y - (k << m)
    acc = np.full_like(frac, c3)
    acc = _shift_round(acc * frac, m) + c2
    acc = _shift_round(acc * frac, m) + c1
    acc = _shift_round(acc * frac, m) + c0
    kc = np.clip(k, -f.bits, f.bits)
    pos = np.minimum(np.maximum(kc, 0), f.bits - 1)
    neg = np.maximum(-kc, 0)
    up = acc << pos
    up = np.where((up >> pos) != acc, f.hi, up)
    down = acc >> np.minimum(neg, f.bits + m)
    out = np.where(kc >= 0, up, down)
    out = np.where(k >= f.bits - 1 - m, f.hi, out)
    return f.sat(out)


def _qdiv(a: np.ndarray, b: np.ndarray, f: Qfmt) -> np.ndarray:
    wa = a << f.m
    safe = np.where(b == 0, 1, b)
    sign = np.where((wa < 0) != (safe < 0), -1, 1)
    q = sign * (np.abs(wa) // np.abs(safe))
    rem = wa - q * safe
    q = q + (np.abs(rem) * 2 >= np.abs(safe)) * sign
    q = np.where(b == 0, np.where(wa >= 0, f.hi, f.lo), q)
    return f.sat(q)


def qsigmoid(x: np.ndarray, f: Qfmt) -> np.ndarray:
    one = min(1 << f.m, f.hi)
    e = _qexp(f.sat(-np.abs(x)), f)
    pos = _qdiv(np.full_like(e, one), f.sat(one + e), f)
    return np.where(x >= 0, pos, f.sat(one - pos))


def fixed_point_classes(x: np.ndarray, weights: Sequence[np.ndarray],
                        biases: Sequence[np.ndarray], bits: int, frac: int,
                        block: int = 16384) -> np.ndarray:
    """Classes of the fixed-point MLP, ``block`` rows at a time.  The dots
    run in float64, which holds every sum of products of 16-bit values
    over fewer than 2^21 terms exactly."""
    f = Qfmt(bits, frac)
    qw = [f.quantize(w).astype(np.float64) for w in weights]
    qb = [f.quantize(b) for b in biases]
    out = []
    for i in range(0, len(x), block):
        h = f.quantize(x[i:i + block])
        for j, (w, b) in enumerate(zip(qw, qb)):
            acc = (h.astype(np.float64) @ w).astype(np.int64)
            h = f.sat(f.sat(_shift_round(acc, f.m)) + b)
            if j < len(qw) - 1:
                h = qsigmoid(h, f)
        out.append(np.argmax(h, axis=-1))
    return np.concatenate(out).astype(np.int32)


def logit_gap(logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far each served class's reference logit lies below the
    reference's best (0 where the served class is a best one; infinite
    where it is no class at all)."""
    served = np.asarray(served, np.int64)
    valid = (served >= 0) & (served < logits.shape[-1])
    picked = logits[np.arange(len(served)), np.where(valid, served, 0)]
    return np.where(valid, logits.max(-1) - picked, np.inf)
