"""Open-loop Poisson arrivals of small requests over a Zipf-skewed fleet.

Requests are sent on a schedule whether or not earlier ones are done.
Every seed gets the same work in another order, so seeds do not change
the load: the gaps between arrivals are the exponential distribution's
``N`` quantiles at midpoints (``N`` = rate x seconds), scaled to fill the
window exactly, then shuffled by the seed; member ``rank`` r receives
``N * r^-s / H`` requests (largest remainders fill the rounding), in an
order the seed shuffles, and the seed also draws which member holds
which rank.  ``s = 0`` or one member is plain Poisson to one endpoint.

The gap draw follows ``benchmarks/serve_http.py``'s open-loop arrival
traces (exponential gaps from a seeded generator); quantiles replace its
random draws so that the multiset of gaps is fixed.
"""

from __future__ import annotations

import numpy as np


def zipf_counts(n: int, n_members: int, s: float) -> np.ndarray:
    """Requests per rank: ``n * r^-s / H(s)``, rounded by largest
    remainders so they sum to ``n``."""
    w = 1.0 / np.arange(1, n_members + 1, dtype=np.float64) ** float(s)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def schedule(params: dict, seed: int, n_rows: int, n_members: int,
             seconds: float, rate_per_s: float = None) -> dict:
    """The window's requests: due times (s from window start, ascending),
    the member and dataset row of each.

    Returns ``{"due": (N,) float64, "member": (N,) int64,
    "row": (N, request_rows) int64}``.
    """
    rate = float(params["rate_per_s"] if rate_per_s is None else rate_per_s)
    n = max(1, int(round(rate * float(seconds))))
    rng = np.random.default_rng([int(seed), 2])
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= float(seconds) / gaps.sum()
    gaps = rng.permutation(gaps)
    # Request k is due after the k gaps before it: the first at 0, the
    # last one gap short of the window's end.
    due = np.concatenate(([0.0], np.cumsum(gaps)[:-1]))
    ranks = np.repeat(np.arange(n_members),
                      zipf_counts(n, n_members, params.get("zipf_s", 0.0)))
    member_of_rank = rng.permutation(n_members)
    member = member_of_rank[rng.permutation(ranks)]
    rows = rng.integers(0, n_rows, (n, int(params.get("request_rows", 1))))
    return {"due": due, "member": member, "row": rows}
