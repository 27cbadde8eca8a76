"""Whole step's share of the chip's int8 peak for the RBF SVM: nominal
operations per row (``work/svm_model.py``) times rows completed per second
of the window, over the peak."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    w = ctx["window"]
    ops = ctx["work"]["svm_model"].ops_per_row(ctx["widths"]) * w["rows"]
    return 100.0 * ops / w["window_s"] / ctx["peak"]["int8_ops_per_s"]
