"""Requests per stacked round of the fleet coalescer (serve/fleet.py),
from its counters differenced over the window."""


def read(ctx):
    c = ctx["counters"]
    return c["stacked_requests"] / c["rounds"] if c["rounds"] else None
