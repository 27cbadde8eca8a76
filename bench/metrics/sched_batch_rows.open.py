"""Rows per dispatch of the micro-batcher (serve/batching.py): the
endpoints' row and batch counters, differenced over the window."""


def read(ctx):
    c = ctx["counters"]
    return c["rows"] / c["batches"] if c["batches"] else None
