"""Closed-loop bulk scoring: one caller, each request a block of rows.

The caller sends its next request only when the last one has returned.
The work is the same for every seed: ``distinct_requests`` blocks of
``request_rows`` rows are drawn from the dataset by the seed before the
window, and the window cycles through them in an order the seed also
draws.
"""

from __future__ import annotations

import numpy as np


def schedule(params: dict, seed: int, n_rows: int, n_members: int = 1) -> dict:
    """Row indices of each distinct request and the order they are sent in.

    Returns ``{"requests": [(request_rows,) int64 row indices, ...],
    "order": infinite-cycle order as an int array of request ids}``.
    """
    rng = np.random.default_rng([int(seed), 1])
    k, n = int(params["distinct_requests"]), int(params["request_rows"])
    requests = [rng.integers(0, n_rows, n) for _ in range(k)]
    order = rng.permutation(k)
    return {"requests": requests, "order": order}
