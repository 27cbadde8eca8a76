"""Helpers shared by the classifier lowerings."""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fxp
from repro.core.fixedpoint import STATS_DTYPE, FxpFormat, FxpStats

__all__ = ["zero_stats", "q", "qx_with_stats", "nbytes", "elem_bytes",
           "resolve_formats", "check_pallas_container"]


def zero_stats() -> FxpStats:
    # Explicitly the shared counter dtype: the old ``jnp.int64`` spelling
    # silently downgraded to int32 with x64 disabled (see
    # fixedpoint.STATS_DTYPE for the portability contract).
    z = jnp.zeros((), STATS_DTYPE)
    return FxpStats(z, z, z)


def q(x: np.ndarray, fmt: FxpFormat) -> jax.Array:
    """Quantize static parameters (no stats — parameters are audited once)."""
    return fxp.quantize(jnp.asarray(x, jnp.float32), fmt)


def qx_with_stats(x: jax.Array, fmt: FxpFormat) -> Tuple[jax.Array, FxpStats]:
    return fxp.quantize_with_stats(x, fmt)


def nbytes(*arrays) -> int:
    return int(sum(np.asarray(a).nbytes for a in arrays))


def elem_bytes(fmt: FxpFormat | None) -> int:
    return 4 if fmt is None else fmt.total_bits // 8


def resolve_formats(target, plan) -> Optional[Callable[[str], FxpFormat]]:
    """Per-tensor format lookup for a lowering: ``F(path) -> FxpFormat``.

    Calibrated targets resolve each path through the QuantPlan (KeyError on
    a path calibration never recorded — a lowering/calibrate drift bug);
    fixed targets serve the Target's single global format for every path,
    which keeps each lowering to ONE code path for both worlds.  Returns
    None for float targets.
    """
    if target.is_calibrated:
        if plan is None:
            raise ValueError(
                f"Target '{target.number_format}' needs a QuantPlan; compile "
                f"through repro.compile with a calibration batch")
        if plan.total_bits != target.container_bits:
            raise ValueError(
                f"QuantPlan container width {plan.total_bits} does not match "
                f"Target '{target.number_format}'")
        return plan.fmt
    fixed = target.fmt
    if fixed is None:
        return None
    return lambda path: fixed


def check_pallas_container(target, fmt: FxpFormat) -> None:
    """Refuse a 32-bit container on the ``pallas`` backend on a TPU.

    Its kernels need int64 intermediates, which Mosaic cannot hold, so no
    exact chip program exists; the Target fails at compile time instead of
    running somewhere else.  Interpret mode (off the TPU) runs it exactly.
    """
    from repro.kernels import tune

    if fmt.total_bits == 32 and tune.on_tpu():
        raise NotImplementedError(
            f"{target!r} cannot run on a TPU: 32-bit containers need int64 "
            f"intermediates that the Pallas TPU compiler cannot hold; use "
            f"backend='xla' or a 16/8-bit number format")
