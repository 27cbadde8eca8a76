"""Shared arithmetic of the ``*_roofline`` readers: the least time the
chip could take for the kernel's nominal work over its device time."""


def share(ctx, kernel, rows, members=None):
    t = ctx["trace"]
    if t is None or ctx["peak"] is None:
        return None
    k = t["kernels"].get(kernel.KERNEL)
    if not k or not k["calls"] or k["time_s"] <= 0 or rows <= 0:
        return None
    args = (ctx["widths"], ctx["bits"], rows, k["calls"])
    ops, nbytes = (kernel.work(*args, members) if members
                   else kernel.work(*args))
    from bench.peaks import least_time

    least, _ = least_time(ops, nbytes, ctx["peak"])
    return 100.0 * least / k["time_s"]
