"""What the harness needs of a configuration of kind ``mlp``: its float
parameters from the configuration's weight seed, the model the program
serves, and the comparison of served classes with the plain reference
(``bench/reference.py``).

A configuration names its kind (``"kind": "mlp"``); another kind brings
its own ``kinds/<kind>.py`` with the same three functions.
"""

from __future__ import annotations

import numpy as np

from bench import reference as R


def params(config: dict, member: int = 0) -> tuple:
    """Member ``member``'s float weights and biases, from the
    configuration's weight seed: glorot normal times ``gain``, biases
    normal with ``bias_std``."""
    w = config["weights"]
    rng = np.random.default_rng([int(w["seed"]), int(member)])
    widths = config["widths"]
    weights, biases = [], []
    for a, b in zip(widths, widths[1:]):
        std = float(w["gain"]) * np.sqrt(2.0 / (a + b))
        weights.append((rng.standard_normal((a, b)) * std).astype(np.float32))
        biases.append((rng.standard_normal(b) * float(w["bias_std"]))
                      .astype(np.float32))
    return weights, biases


def model(p: tuple):
    """The program's model object for parameters ``p``."""
    from repro.models import MLPModel

    return MLPModel(*p)


def compare(config: dict, rows: np.ndarray, members: list,
            served: np.ndarray, row: np.ndarray, member: np.ndarray) -> dict:
    """The numbers compared, each as ``(value, limit)``: served class
    ``served[i]`` answered dataset row ``rows[row[i]]`` for member
    ``member[i]``, whose parameters are ``members[member[i]]``.

    ``fixed_point_exact``: the configuration states its arithmetic, so
    the reference is that arithmetic and every served class must equal
    it.  ``float_logit_gap``: the widest gap by which a served class's
    float64 reference logit lies below the reference's best."""
    if config["compare"] == "fixed_point_exact":
        fmt = (config["q_format"]["bits"], config["q_format"]["frac"])
        differ = 0
        for e in np.unique(member):
            ref = R.fixed_point_classes(rows, *members[e], *fmt)
            sel = member == e
            differ += int(np.sum(served[sel] != ref[row[sel]]))
        return {"rows_differ": (differ, 0)}
    gap = 0.0
    for e in np.unique(member):
        sel = member == e
        logits = R.mlp_logits(rows[row[sel]], *members[e])
        gap = max(gap, float(R.logit_gap(logits, served[sel]).max()))
    return {"widest_logit_gap": (gap,
                                 config["limits"]["widest_logit_gap"])}
