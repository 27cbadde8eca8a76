"""The chip's published peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PATH) -> dict:
    """The peak table's row for ``device_kind``; a device that is not in
    the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time(ops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take: the larger of operations over
    the int8 peak and bytes over HBM bandwidth, and which of the two binds.
    Every integer container is held to the int8 peak (the v5e MXU has no
    int16 path)."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
