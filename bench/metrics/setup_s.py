"""Seconds from process start to the window's start: start-up, building
and compiling (or loading from the cache) the cell's programs, warm-up."""


def read(ctx):
    return ctx["setup_s"]
