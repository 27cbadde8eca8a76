"""Straggler hold per fleet round: the ``repro.fleet.hold`` time over
the ``repro.fleet.round`` spans that took requests."""

from bench.metrics._spans import hold_per_round_ms, records


def read(ctx):
    return hold_per_round_ms(records(ctx))
