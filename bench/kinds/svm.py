"""What the harness needs of a configuration of kind ``svm`` (an RBF
kernel SVM): its float parameters from the configuration's weight seed,
the model the program serves, and the comparison of served classes with
the plain reference (``bench/reference_svm.py``).

Parameters, all from ``(weights.seed, member)``: ``per_class`` prototypes
of each class, drawn without replacement from the dataset's training rows
of that class (class-stratified, as the repo's kernel-SVM trainer picks
them); gamma = 1 / (features x variance of the training rows), sklearn's
"scale"; dual coefficients normal with ``dual_std``, plus ``own_class``
where the column is the prototype's own class (each prototype votes for
its class, so that served rows spread over the classes); intercepts normal
with ``bias_std``.
"""

from __future__ import annotations

import numpy as np

from bench import reference as R
from bench import reference_svm as RS


def params(config: dict, member: int = 0) -> tuple:
    """Member ``member``'s (support vectors, dual, intercept, gamma)."""
    from repro.data import load_dataset

    w = config["weights"]
    ds = load_dataset(config["dataset"])
    x = np.asarray(ds.x_train, np.float64)
    y = np.asarray(ds.y_train)
    n_feat, n_sv, n_cls = config["widths"]
    per = n_sv // n_cls
    rng = np.random.default_rng([int(w["seed"]), int(member)])
    pick = np.concatenate([rng.choice(np.flatnonzero(y == c), per,
                                      replace=False) for c in range(n_cls)])
    sv = x[pick]
    own = np.repeat(np.arange(n_cls), per)[:, None] == np.arange(n_cls)
    dual = (rng.standard_normal((len(pick), n_cls)) * float(w["dual_std"])
            + float(w["own_class"]) * own)
    intercept = rng.standard_normal(n_cls) * float(w["bias_std"])
    gamma = 1.0 / (n_feat * x.var())
    return sv, dual, intercept, gamma


def model(p: tuple):
    """The program's model object for parameters ``p``."""
    from repro.models.svm import SVMModel

    sv, dual, intercept, gamma = p
    return SVMModel("rbf", support_vectors=sv, dual_coef=dual,
                    intercept=intercept, gamma=gamma)


def compare(config: dict, rows: np.ndarray, members: list,
            served: np.ndarray, row: np.ndarray, member: np.ndarray) -> dict:
    """``float_logit_gap``: the widest gap by which a served class's
    float64 reference logit lies below the reference's best, over every
    served row (see ``bench/kinds/mlp.py`` for the arguments).  Each
    distinct (row, class) pair is computed once: a window serves its
    dataset rows many times over."""
    if config["compare"] != "float_logit_gap":
        raise ValueError(f"kind svm compares float_logit_gap, not "
                         f"{config['compare']!r}")
    served = np.asarray(served, np.int64)
    row = np.asarray(row, np.int64)
    # a served class outside [0, n_cls) keeps its own code, -1 or n_cls
    base = config["widths"][-1] + 2
    gap = 0.0
    for e in np.unique(member):
        sel = member == e
        code = np.clip(served[sel], -1, base - 2) + 1
        pairs = np.unique(row[sel] * base + code)
        u_row, u_cls = pairs // base, pairs % base - 1
        rows_u, inv = np.unique(u_row, return_inverse=True)
        logits = RS.rbf_logits(rows[rows_u], *members[e])
        gap = max(gap, float(R.logit_gap(logits[inv], u_cls).max()))
    return {"widest_logit_gap": (gap, config["limits"]["widest_logit_gap"])}
