"""Rows completed in the window over the window's length (host clock;
the window ends when the last request sent in it returns)."""


def read(ctx):
    w = ctx["window"]
    return w["rows"] / w["window_s"]
