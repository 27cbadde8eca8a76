"""The plain reference of the RBF kernel SVM, in numpy alone.

Nothing here imports the program or takes anything it made: the support
vectors, dual coefficients, intercepts and gamma are the benchmark's own,
drawn from the configuration (``bench/kinds/svm.py``).

:func:`rbf_logits` is libsvm's decision function in float64,
``sum_m dual[m, c] * exp(-gamma * |x - sv_m|^2) + b[c]``, computed in
blocks of rows so that a whole window's rows fit.  The squared distance is
``|x|^2 - 2 x.sv + |sv|^2``, clamped at 0 (float64 cancellation can leave
it a hair below 0 for a row equal to a support vector).
"""

from __future__ import annotations

import numpy as np


def rbf_logits(x: np.ndarray, sv: np.ndarray, dual: np.ndarray,
               intercept: np.ndarray, gamma: float,
               block: int = 4096) -> np.ndarray:
    sv = np.asarray(sv, np.float64)
    dual = np.asarray(dual, np.float64)
    b = np.asarray(intercept, np.float64)
    sv2 = np.sum(sv * sv, axis=-1)
    out = []
    for i in range(0, len(x), block):
        xb = np.asarray(x[i:i + block], np.float64)
        d2 = np.sum(xb * xb, axis=-1)[:, None] - 2.0 * (xb @ sv.T) + sv2
        out.append(np.exp(-float(gamma) * np.maximum(d2, 0.0)) @ dual + b)
    return (np.concatenate(out) if out
            else np.zeros((0, dual.shape[1]), np.float64))
