"""Production mesh construction (assignment MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module constant: importing this module never touches jax
device state — jax locks the device count on first backend init, and smoke
tests must see the real single CPU device while the dry-run sees 512
placeholder host devices (set via XLA_FLAGS in dryrun.py *before* any
import).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_ci_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto mode: the sharding rules
    place arrays with NamedSharding constraints and let the partitioner
    resolve the rest (``jax.make_mesh`` now defaults to Explicit axes,
    under which e.g. an embedding gather needs an explicit out sharding).
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: ``data`` carries in-pod DP/FSDP/SP; ``model`` carries TP/EP/vocab;
    ``pod`` (multi-pod) is pure DP so the slower inter-pod link only sees the
    once-per-step gradient all-reduce.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_ci_mesh(n_devices: int = 8):
    """Small mesh for CI-scale dry-run tests (data x model)."""
    d = max(1, n_devices // 2)
    return make_mesh((d, n_devices // d), ("data", "model"))
