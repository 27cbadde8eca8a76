"""The calibrated RBF kernel SVM's chain of formats (compile/lowerings/svm.py).

* a small seeded RBF SVM whose squared distances leave the 16-bit range
  serves the same bits on ``ref``, ``xla``, ``pallas`` (megakernel and the
  chained path past the VMEM budget) and the emitted C, at ``auto16`` and
  ``auto8``, on rows inside and far outside the calibration range, and its
  logits lie within a written tolerance of the float64 decision function;
* at D6 widths (561 features, 300 prototypes, 6 classes) the calibrated
  model serves the float model's classes, where the paper's single 16-bit
  format serves one class for every row;
* the pieces: the planner's int32 accumulator rule, ``scale_acc`` against
  exact arithmetic, and ``qexp`` from one format into another.
"""

import os

import numpy as np
import pytest

from repro import emit as E
from repro.compile import Target, compile
from repro.core import fixedpoint as fxp
from repro.kernels import ref as ref_ops
from repro.models.svm import SVMModel
from repro.quant import Calibration, plan_formats

F, S, C = 12, 16, 3
BACKENDS = ("ref", "xla", "pallas", "chained", "emit")
# Widest |served logit - float64 logit| over the rows below.  16 bits: the
# exponent's 2^-12 step and the exp polynomial's ~1e-4 error move each
# kernel value by about 2e-4, times sum|dual| (about 13 here), plus the
# output's own step; 8 bits: the kernel value's 2^-6 step times sum|dual|.
LOGIT_TOL = {"auto16": 0.01, "auto8": 0.5}


@pytest.fixture(scope="module")
def small():
    """Seeded features and prototypes of scale 30: squared distances reach
    about 6e4, past a 16-bit container at 0 fractional bits."""
    rng = np.random.RandomState(0)
    sv = rng.randn(S, F) * 30.0
    xtr = (rng.randn(400, F) * 30.0).astype(np.float32)
    xte = (rng.randn(64, F) * 30.0).astype(np.float32)
    d2 = ((xtr[:, None, :] - sv[None]) ** 2).sum(-1)
    assert d2.max() > 2 ** 15
    model = SVMModel("rbf", support_vectors=sv, dual_coef=rng.randn(S, C),
                     intercept=rng.randn(C) * 0.1, gamma=1.0 / np.median(d2))
    return model, xtr, xte


def _compile(model, fmt, backend, xtr, monkeypatch):
    if backend == "chained":
        monkeypatch.setenv("REPRO_MEGAKERNEL_VMEM", "0")
        art = compile(model, Target(number_format=fmt, backend="pallas"),
                      calibration=xtr)
        monkeypatch.delenv("REPRO_MEGAKERNEL_VMEM")
        assert art.kernel_strategy == "per-layer"
        return art
    if backend == "emit" and E.find_cc() is None:
        pytest.skip("no C compiler (cc/gcc/clang) on PATH")
    art = compile(model, Target(number_format=fmt, backend=backend),
                  calibration=xtr)
    if backend == "pallas":
        assert art.kernel_strategy == "megakernel"
    return art


@pytest.mark.parametrize("scale", [1.0, 3.0, 100.0],
                         ids=["calibrated", "3x", "100x"])
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("fmt", ["auto16", "auto8"])
def test_small_rbf_is_bit_identical_across_backends(small, monkeypatch, fmt,
                                                    backend, scale):
    """Rows at 3x the calibration scale reach distances past the planned
    headroom, and at 100x past int32: every backend wraps and saturates
    the same way."""
    model, xtr, xte = small
    x = xte * np.float32(scale)
    want = compile(model, Target(number_format=fmt, backend="ref"),
                   calibration=xtr).predict(x)
    got = _compile(model, fmt, backend, xtr, monkeypatch).predict(x)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fmt", ["auto16", "auto8"])
def test_small_rbf_logits_follow_the_float64_model(small, fmt):
    model, xtr, xte = small
    art = compile(model, Target(number_format=fmt, backend="ref"),
                  calibration=xtr)
    spec = art.extras["emit_spec"]
    assert spec["chain"] is not None
    out_fmt = spec["out_fmt"]
    qx = fxp.quantize(xte, spec["fmt"])
    out = ref_ops.fxp_svm_model_ref(
        qx, spec["sv"], spec["dual"], spec["b"], "rbf", spec["fmt"], out_fmt,
        spec["qgamma"], spec["qcoef0"], spec["degree"], spec["dec_shift"],
        spec["chain"])
    logits = np.asarray(out, np.float64) / out_fmt.scale
    want = np.asarray(model.decision(xte))
    assert np.abs(logits - want).max() <= LOGIT_TOL[fmt]
    bits = art.report()["chain_frac_bits"]
    assert bits["distance"] == 2 * spec["fmt"].frac_bits
    assert bits["kernel"] == spec["chain"].kernel_fmt.frac_bits


@pytest.fixture(scope="module")
def d6():
    from repro.data import load_dataset
    from repro.models import train_kernel_svm

    ds = load_dataset("D6")
    model = train_kernel_svm(ds.x_train, ds.y_train, 6, kernel="rbf",
                             n_prototypes=300, epochs=3, seed=0)
    return model, ds.x_train[:4096], ds.x_test[:2048]


@pytest.mark.parametrize("fmt", ["auto16", "fxp16"])
def test_d6_rbf_serves_the_float_models_classes(d6, fmt):
    """The calibrated chain agrees with the float model on at least 99% of
    D6 test rows (all of them, on this seed).  The paper's single Q12.4
    format cannot hold the squared distances (peak near 1e5) and serves
    one class for every row; fixed formats keep that arithmetic."""
    model, cal, x = d6
    want = np.asarray(model.predict(x))
    art = compile(model, Target(number_format=fmt, backend="ref"),
                  calibration=cal if fmt == "auto16" else None)
    got = np.asarray(art.predict(x))
    if fmt == "fxp16":
        assert len(np.unique(got)) == 1
        return
    assert np.mean(got == want) >= 0.99
    plan = art.quant_plan
    assert [plan.frac_bits(p) for p in ("input", "exponent", "kernel")] == [
        6, 12, 14]
    assert art.report()["chain_frac_bits"] == {
        "distance": 12, "exponent": 12, "kernel": 14}


@pytest.mark.parametrize("bits", [8, 16])
def test_int32_accumulator_bounds_the_operands_whatever_the_container(bits):
    calib = Calibration(
        ranges={"a": 0.9, "b": 0.9, "e": 5.0},
        groups=(("a", "b"),), acc_ranges={"d": 9.25e4},
        int32_accs=(("a", "b", "d"),))
    plan = plan_formats(calib, bits)
    fa, fb = plan.frac_bits("a"), plan.frac_bits("b")
    assert fa == fb
    assert 9.25e4 * 2 * 2 ** (fa + fb) <= 2 ** 31 - 1
    # the largest such: one more bit each would pass int32
    assert 9.25e4 * 2 * 2 ** (fa + fb + 2) > 2 ** 31 - 1 or fa == bits - 1
    assert "d" not in plan.paths()


@pytest.mark.parametrize("c, acc_frac, m_out, bits", [
    (6.0e-5, 12, 12, 16), (4.8e-5, 14, 13, 16), (6.0e-5, 2, 4, 8),
    (0.75, 4, 13, 16), (3.0, 0, 14, 16), (0.0, 12, 12, 16)])
def test_scale_acc_rounds_like_exact_arithmetic(c, acc_frac, m_out, bits):
    """Within one step of the output of ``c * acc`` rounded exactly, and
    saturating wherever the exact product does."""
    fmt = fxp.FxpFormat(bits, m_out)
    consts = fxp.acc_scale_consts(c, acc_frac, fmt)
    rng = np.random.RandomState(1)
    acc = np.concatenate([rng.randint(0, 2 ** 31 - 1, 4000),
                          rng.randint(0, 2 ** 20, 4000), [0, 1, 2 ** 31 - 1]])
    got = np.asarray(fxp.scale_acc(np.asarray(acc, np.int32), consts, fmt),
                     np.int64)
    exact = np.clip(np.round(acc * c * 2.0 ** (m_out - acc_frac)),
                    fmt.qmin, fmt.qmax)
    step = 1 + exact * 2.0 ** -14  # the 15-bit multiplier's relative error
    assert np.all(np.abs(got - exact) <= step)
    assert np.all(got[exact == fmt.qmax] == fmt.qmax)


@pytest.mark.parametrize("m_in, m_out, bits", [(12, 14, 16), (13, 14, 16),
                                               (15, 14, 16), (4, 6, 8),
                                               (5, 6, 8)])
def test_qexp_from_one_format_into_another(m_in, m_out, bits):
    fin, fout = fxp.FxpFormat(bits, m_in), fxp.FxpFormat(bits, m_out)
    q = np.arange(fin.qmin, 1, max(1, -fin.qmin // 4096))
    got = np.asarray(fxp.qexp(np.asarray(q, fin.dtype), fin, fout),
                     np.float64) / fout.scale
    want = np.exp(q / fin.scale)
    # the cubic's ~1e-4 relative error, plus one output step
    assert np.all(np.abs(got - want) <= 1.5e-4 * want + 1.0 / fout.scale)
    # flush to zero, never below
    assert got.min() >= 0.0
