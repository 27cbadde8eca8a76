"""Jitted artifacts take their input flat (``repro.compile.api._flat_jit``).

* every jitted single-device artifact gives outputs and stats bit-identical
  to a plain ``jax.jit`` of its lowering's ``predict`` on 2-D rows: mlp
  (megakernel and per-layer), rbf svm, logistic and tree, on ``xla`` and
  ``pallas``, over a bucket ladder;
* non-contiguous host views and a ``jax.Array`` give the same answers;
* a fixed-policy artifact wraps the flat program and answers as before;
* ``pretune`` and then serving trace one program per bucket, no more;
* ``flat_input`` says which artifacts take the flat path: jitted
  single-device and ``fused`` mesh ones, not ``ref``, ``emit`` or ``spmd``.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.compile import Target, compile
from repro.compile.api import _specialize
from repro.models import (train_decision_tree, train_kernel_svm,
                          train_logistic, train_mlp)
from repro.serve import BatchingPolicy, InferenceService

LADDER = (1, 2, 4, 8, 16)


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(5)
    n, f, c = 300, 12, 3
    means = rng.randn(c, f) * 4.0
    y = rng.randint(0, c, n).astype(np.int32)
    x = (means[y] + rng.randn(n, f)).astype(np.float32)
    return x, y, c


@pytest.fixture(scope="module")
def models(data):
    x, y, c = data
    return {
        "mlp": train_mlp(x, y, c, hidden=(16,), epochs=5),
        "svm-rbf": train_kernel_svm(x, y, c, kernel="rbf", n_prototypes=24,
                                    epochs=4),
        "logistic": train_logistic(x, y, c, epochs=5),
        "tree": train_decision_tree(x, y, c, max_depth=5),
    }


# (model, number format, backend, kernel route); a zero VMEM budget sends
# the MLP down the per-layer route.
CASES = [
    ("mlp", "fxp16", "pallas", "megakernel"),
    ("mlp", "fxp16", "pallas", "per-layer"),
    ("mlp", "fxp16", "xla", None),
    ("svm-rbf", "auto16", "pallas", "megakernel"),
    ("svm-rbf", "auto16", "xla", None),
    ("logistic", "fxp16", "pallas", None),
    ("logistic", "fxp16", "xla", None),
    ("tree", "fxp16", "pallas", None),
    ("tree", "fxp16", "xla", None),
]


def _compile(models, data, name, fmt, backend, route=None, monkeypatch=None):
    if route == "per-layer":
        monkeypatch.setenv("REPRO_MEGAKERNEL_VMEM", "0")
    art = compile(models[name], Target(number_format=fmt, backend=backend),
                  calibration=data[0][:128])
    if route is not None:
        assert art.kernel_strategy == route
    return art


def _same(got, want):
    out, stats = got
    w_out, w_stats = want
    np.testing.assert_array_equal(np.asarray(out), np.asarray(w_out))
    for k in ("overflow", "underflow", "total"):
        assert int(getattr(stats, k)) == int(getattr(w_stats, k)), k


@pytest.mark.parametrize("name,fmt,backend,route", CASES)
def test_flat_program_equals_the_2d_program(models, data, monkeypatch, name,
                                             fmt, backend, route):
    art = _compile(models, data, name, fmt, backend, route, monkeypatch)
    assert art.flat_input
    plain = jax.jit(art._program.predict)
    x = data[0]
    for b in LADDER:
        rows = x[b:2 * b]
        _same(art._predict(rows), plain(rows))
        out, stats = art.predict_with_stats(rows)
        w_out, w_stats = plain(rows)
        np.testing.assert_array_equal(out, np.asarray(w_out))
        assert stats["overflow"] == int(w_stats.overflow)
        assert stats["underflow"] == int(w_stats.underflow)
        assert stats["total"] == int(w_stats.total)
        np.testing.assert_array_equal(art.predict(rows), np.asarray(w_out))


@pytest.mark.parametrize("form", ["row_stride", "column_major", "jax_array"])
@pytest.mark.parametrize("name,fmt,backend", [
    ("mlp", "fxp16", "pallas"), ("svm-rbf", "auto16", "xla")])
def test_flat_program_takes_views_and_device_arrays(models, data, name, fmt,
                                                    backend, form):
    art = _compile(models, data, name, fmt, backend)
    x = data[0][:32]
    given = {"row_stride": x[::2],
             "column_major": np.asfortranarray(x[:16]),
             "jax_array": jax.device_put(x[:16])}[form]
    if form != "jax_array":
        assert not given.flags.c_contiguous
    want = jax.jit(art._program.predict)(np.ascontiguousarray(given))
    _same(art._predict(given), want)


def test_fixed_policy_wraps_the_flat_program(models, data):
    x = data[0]
    dyn = compile(models["mlp"], Target(number_format="fxp16",
                                        backend="pallas"))
    fixed = compile(models["mlp"], Target(number_format="fxp16",
                                          backend="pallas",
                                          batch_policy="fixed",
                                          batch_size=16))
    assert fixed.flat_input
    for n in (1, 5, 16):
        np.testing.assert_array_equal(fixed.predict(x[:n]),
                                      dyn.predict(x[:n]))
        out, stats = fixed.predict_with_stats(x[:n])
        w_out, w_stats = dyn.predict_with_stats(x[:n])
        np.testing.assert_array_equal(out, w_out)
        assert stats == w_stats


def test_pretune_then_serving_traces_one_program_per_bucket(models, data):
    """The warm-up, ``predict_with_stats`` and every served batch hit the
    executables ``pretune`` built: one trace per bucket."""
    x = data[0]
    art = compile(models["mlp"], Target(number_format="fxp16",
                                        backend="pallas"))
    traced = []

    def counting(rows):
        traced.append(rows.shape)
        return art._program.predict(rows)

    predict = _specialize(
        dataclasses.replace(art._program, predict=counting), art.target,
        art.kind)
    art = dataclasses.replace(art, _predict=predict)
    policy = BatchingPolicy(max_batch=16, max_wait_ms=1)
    buckets = policy.buckets()
    art.pretune(x[:1], batches=buckets)
    assert sorted(traced) == sorted((b, x.shape[1]) for b in buckets)
    svc = InferenceService()
    try:
        svc.register("mlp", artifact=art, policy=policy)
        futs = [svc.submit("mlp", x[i:i + 1 + i % 5]) for i in range(40)]
        for f in futs:
            f.result(timeout=120)
        np.testing.assert_array_equal(
            svc.predict("mlp", x[:100]),
            np.asarray(jax.jit(art._program.predict)(x[:100])[0]))
        for b in buckets:
            art.predict_with_stats(np.ascontiguousarray(x[:b]))
    finally:
        svc.close()
    assert len(traced) == len(buckets), traced


@pytest.mark.parametrize("backend,mesh_strategy,flat", [
    ("xla", None, True),
    ("pallas", None, True),
    ("ref", None, False),
    ("emit", None, False),
    ("xla", "spmd", False),
    ("xla", "fused", True),
])
def test_flat_input_follows_the_dispatch_path(models, data, backend,
                                              mesh_strategy, flat):
    art = compile(models["logistic"], Target(number_format="fxp16",
                                             backend=backend))
    if mesh_strategy is not None:
        from repro.sharding import rules as shrules

        art = art.specialize_mesh(shrules.make_serving_mesh(1), mesh_strategy)
        x = data[0][:6]
        np.testing.assert_array_equal(
            art.predict(x), compile(models["logistic"],
                                    Target(number_format="fxp16",
                                           backend="ref")).predict(x))
    assert art.flat_input is flat
