"""Nominal work of the stacked fleet kernel: each call reads every
member's weights once; each real row costs its member's MLP.  Padded
slots and padded rows are not work."""

from __future__ import annotations

from typing import Sequence

from bench.work import mlp_model

KERNEL = "fxp_mlp_fleet"


def work(widths: Sequence[int], bits: int, rows: int, calls: int,
         members: int) -> tuple:
    """(operations, bytes) of ``calls`` stacked calls of ``members``
    models over ``rows`` real rows in all."""
    e = int(bits) // 8
    ops = mlp_model.ops_per_row(widths) * int(rows)
    nbytes = (int(calls) * int(members) * mlp_model.param_bytes(widths, bits)
              + int(rows) * (int(widths[0]) + int(widths[-1])) * e)
    return ops, nbytes
