"""Nominal work of the kernel-SVM megakernel (RBF): what the model's shapes
need, not what the lane-padded, limb-split implementation computes.

``widths`` = (features F, support vectors S, classes C).  Per row:
``2 * S * (F + C) + 2 * F`` operations (the x.sv dot, the decision dot and
the row's squared norm; the support vectors' norms, the exp and the
elementwise chain are not counted).  Per call: support vectors, dual
coefficients and intercepts read once; each row's input features and
output logits, all at the container's width.
"""

from __future__ import annotations

from typing import Sequence

# Substring of the kernel's name in the device trace.
KERNEL = "fxp_svm_model"


def ops_per_row(widths: Sequence[int]) -> int:
    f, s, c = (int(v) for v in widths)
    return 2 * s * (f + c) + 2 * f


def param_bytes(widths: Sequence[int], bits: int) -> int:
    f, s, c = (int(v) for v in widths)
    return int(bits) // 8 * (s * f + s * c + c)


def work(widths: Sequence[int], bits: int, rows: int, calls: int) -> tuple:
    """(operations, bytes) of ``calls`` kernel calls over ``rows`` rows."""
    f, c = int(widths[0]), int(widths[-1])
    ops = ops_per_row(widths) * int(rows)
    nbytes = (int(calls) * param_bytes(widths, bits)
              + int(rows) * (f + c) * (int(bits) // 8))
    return ops, nbytes
