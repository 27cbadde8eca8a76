"""Kernel-SVM megakernel (kernels/fxp_model.py): least time for the nominal
work of the rows it served over its device time in the trace."""

from bench.metrics._roofline import share


def read(ctx):
    return share(ctx, ctx["work"]["svm_model"], ctx["counters"]["rows"])
