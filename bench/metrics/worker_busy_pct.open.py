"""Share of the window in which the serving thread (an endpoint's
micro-batcher or the fleet coalescer) is inside a ``repro.batch`` or
``repro.fleet.round`` span, less its ``repro.fleet.hold`` time."""

from bench.metrics._spans import busy_pct, records


def read(ctx):
    w = ctx["window"]
    return busy_pct(records(ctx), w["t0"], w["t1"])
