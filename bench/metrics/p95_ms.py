"""95th percentile (nearest rank) of the same latencies as ``p50_ms``."""

import math

import numpy as np


def read(ctx):
    lat = np.sort(ctx["window"]["latency_s"])
    v = float(lat[max(0, math.ceil(0.95 * len(lat)) - 1)]) * 1e3
    return v if math.isfinite(v) else None
