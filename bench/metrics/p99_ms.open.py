"""99th percentile (nearest rank) of the same latencies as ``p50_ms``,
from the traced run.  It is where the serving process's whole-process
pauses show first: a pause of about 115 ms holds up every request due in
it, which lifts the 99th percentile in some runs and not in others."""

import math

import numpy as np


def read(ctx):
    lat = np.sort(ctx["window"]["latency_s"])
    v = float(lat[max(0, math.ceil(0.99 * len(lat)) - 1)]) * 1e3
    return v if math.isfinite(v) else None
