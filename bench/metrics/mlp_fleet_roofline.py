"""Stacked fleet kernel (kernels/fxp_model.py): least time for the
nominal work of the rows that rode stacked rounds over its device time."""

from bench.metrics._roofline import share


def read(ctx):
    return share(ctx, ctx["work"]["mlp_fleet"],
                 ctx["counters"]["coalesced_rows"], members=ctx["members"])
