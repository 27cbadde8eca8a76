"""Host time per dispatch, over the endpoints' own dispatches and the
fleet's stacked ones (``device_s`` of batchers and coalescer over their
dispatch counts).  The coalescer's time runs from launch to the round's
finalization, which waits for the next round to launch."""


def read(ctx):
    c = ctx["counters"]
    solo = c["batches"] - c["coalesced_batches"]
    n = solo + c["stacked_dispatches"]
    if not n:
        return None
    return 1e3 * (c["solo_device_s"] + c["fleet_device_s"]) / n
