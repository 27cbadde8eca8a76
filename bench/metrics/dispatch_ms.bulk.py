"""Host time per dispatch, from the artifact's predict call to its
materialized outputs (the batcher's ``device_s`` over its dispatches)."""


def read(ctx):
    c = ctx["counters"]
    return 1e3 * c["solo_device_s"] / c["batches"] if c["batches"] else None
