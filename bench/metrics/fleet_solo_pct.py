"""Share of the members' batches that the coalescer served on the
member's own path instead of the stacked dispatch, over the window."""


def read(ctx):
    c = ctx["counters"]
    return 100.0 * c["solo_batches"] / c["batches"] if c["batches"] else None
