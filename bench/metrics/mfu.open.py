"""Whole step's share of the chip's int8 peak: nominal operations per
row times rows completed per second of the window, over the peak."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    w = ctx["window"]
    ops = ctx["work"]["mlp_model"].ops_per_row(ctx["widths"]) * w["rows"]
    return 100.0 * ops / w["window_s"] / ctx["peak"]["int8_ops_per_s"]
