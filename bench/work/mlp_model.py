"""Nominal work of the whole-MLP kernel: what the model's shapes need,
not what the lane-padded, limb-split implementation computes.

Per row: ``2 * sum(in_i * out_i)`` operations.  Per call: every weight
and bias read once, each row's input features and output logits, all at
the container's width.
"""

from __future__ import annotations

from typing import Sequence

# Substring of the kernel's name in the device trace.
KERNEL = "fxp_mlp_model"


def ops_per_row(widths: Sequence[int]) -> int:
    return 2 * sum(int(a) * int(b) for a, b in zip(widths, widths[1:]))


def param_bytes(widths: Sequence[int], bits: int) -> int:
    e = int(bits) // 8
    return e * sum(int(a) * int(b) + int(b) for a, b in zip(widths, widths[1:]))


def work(widths: Sequence[int], bits: int, rows: int, calls: int) -> tuple:
    """(operations, bytes) of ``calls`` kernel calls over ``rows`` rows."""
    e = int(bits) // 8
    ops = ops_per_row(widths) * int(rows)
    nbytes = (int(calls) * param_bytes(widths, bits)
              + int(rows) * (int(widths[0]) + int(widths[-1])) * e)
    return ops, nbytes
