"""Median latency of every request due in the window, from its due time
to its answer; a failed or unanswered request counts as missing."""

import math

import numpy as np


def read(ctx):
    lat = np.sort(ctx["window"]["latency_s"])
    v = float(lat[max(0, math.ceil(0.50 * len(lat)) - 1)]) * 1e3
    return v if math.isfinite(v) else None
