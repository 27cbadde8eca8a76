"""Median wait of a request in its endpoint's queue, from the enqueue to
the moment a batch or a fleet round takes it (``repro.request.queue``)."""

from bench.metrics._spans import median_ms, records


def read(ctx):
    return median_ms(records(ctx), "repro.request.queue")
