"""The ``svm`` kind and the ``har_svm_rbf_auto16.bulk`` cell: found by name,
its nominal work and readers against hand arithmetic, and a whole run at
test size on the CPU (chip check skipped) that is correct when sound and
not correct under the control, three planted faults and the paper's
single-format arithmetic."""

import json
import os

import numpy as np
import pytest

from bench import cells
from bench import reference as R
from bench import reference_svm as RS
from bench.work import svm_model
from test_correct import _fault, _run, altered, half_left_out, misrouted

CELL = "har_svm_rbf_auto16.bulk"
D6 = (561, 300, 6)


def test_cell_config_kind_work_and_metrics_are_found_by_name():
    cell = cells.Cell(CELL)
    assert cell.config["kind"] == "svm" and cell.config["widths"] == list(D6)
    assert cell.traffic["generator"] == "closed_bulk"
    assert cells.work_modules()["svm_model"].KERNEL == "fxp_svm_model"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"svm_model_roofline.bulk", "mfu.svm_bulk", "idle_pct.bulk",
            "dispatch_call_ms.bulk", "dispatch_sync_ms.bulk"} <= names
    assert not names & {"mlp_model_roofline.bulk", "mfu.bulk",
                        "dispatch_ms.bulk"}
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "rows_per_s", "setup_s"]
    sv, dual, icept, gamma = cell.kind.params(cell.config)
    assert sv.shape == (300, 561) and dual.shape == (300, 6)
    assert icept.shape == (6,) and 0 < gamma < 1e-3
    # the same weights on every call: they come from the file's seed
    np.testing.assert_array_equal(cell.kind.params(cell.config)[1], dual)


def test_d6_ops_and_bytes_one_bulk_dispatch():
    # 2 * 300 * (561 + 6) + 2 * 561
    assert svm_model.ops_per_row(D6) == 341322
    ops, nbytes = svm_model.work(D6, 16, rows=4096, calls=1)
    assert ops == 341322 * 4096
    # (300*561 + 300*6 + 6) * 2 B once, (561 + 6) * 2 B a row
    assert nbytes == 340212 + 4096 * 1134


def test_small_shape_by_hand():
    ops, nbytes = svm_model.work((4, 3, 2), 8, rows=5, calls=2)
    assert ops == 5 * (2 * 3 * (4 + 2) + 2 * 4)
    assert nbytes == 2 * (12 + 6 + 2) + 5 * 6


def test_readers_on_a_hand_made_context():
    work = cells.work_modules()
    trace = {"kernels": {"fxp_svm_model": {"time_s": 2e-3, "calls": 10}}}
    peak = {"int8_ops_per_s": 3.93e14, "hbm_bytes_per_s": 8.19e11}
    ctx = {"trace": trace, "peak": peak, "widths": list(D6), "bits": 16,
           "work": work, "counters": {"rows": 40960},
           "window": {"rows": 40960, "window_s": 2.0}}
    cell = cells.Cell(CELL)
    ops, nbytes = svm_model.work(D6, 16, 40960, 10)
    least = max(ops / 3.93e14, nbytes / 8.19e11)
    assert cell.reader("svm_model_roofline.bulk")(ctx) == pytest.approx(
        100.0 * least / 2e-3)
    assert cell.reader("mfu.svm_bulk")(ctx) == pytest.approx(
        100.0 * 341322 * 40960 / 2.0 / 3.93e14)
    # a trace with no such kernel, and a run with no chip: nothing to read
    assert cell.reader("svm_model_roofline.bulk")(
        dict(ctx, trace={"kernels": {}})) is None
    assert cell.reader("mfu.svm_bulk")(dict(ctx, peak=None)) is None


def test_reference_blocks_and_compare_by_distinct_row():
    """The reference's row blocks give the unblocked result, and the
    comparison over repeated rows equals the one over every served row."""
    cell = cells.Cell(CELL)
    p = cell.kind.params(cell.config)
    rows = np.random.RandomState(0).randn(300, 561).astype(np.float32)
    whole = RS.rbf_logits(rows, *p, block=1 << 20)
    np.testing.assert_allclose(RS.rbf_logits(rows, *p, block=7), whole,
                               rtol=1e-12, atol=1e-12)
    row = np.random.RandomState(1).randint(0, 300, 5000)
    served = np.random.RandomState(2).randint(0, 6, 5000)
    served[:3] = (-1, 6, 9)
    got = cell.kind.compare(cell.config, rows, [p], served, row,
                            np.zeros(5000, np.int64))["widest_logit_gap"][0]
    assert got == float(R.logit_gap(whole[row], served).max()) == np.inf
    got = cell.kind.compare(cell.config, rows, [p], served[3:], row[3:],
                            np.zeros(4997, np.int64))["widest_logit_gap"][0]
    assert got == float(R.logit_gap(whole[row[3:]], served[3:]).max())


def test_sound_run_is_correct(small_bench, capsys):
    res = _run(small_bench, capsys, CELL)
    assert res["correct"] is True, res["checked"]
    assert res["failed"] == 0
    assert res["checked"]["widest_logit_gap"]["value"] == 0.0


@pytest.mark.parametrize("number_format", ["auto8", "fxp16"],
                         ids=["control", "single_format"])
def test_lower_precision_is_not_correct(small_bench, capsys, number_format):
    """The control (auto8), and the paper's one Q12.4 format for every
    tensor, the arithmetic the calibrated plan ran before it had a chain
    (then at 0 fractional bits): neither is correct."""
    cfg = cells.Cell(CELL, bench_dir=small_bench).config
    assert cfg["control"]["number_format"] == "auto8"
    res = _run(small_bench, capsys, CELL, number_format=number_format)
    assert res["correct"] is False, res["checked"]


@pytest.mark.parametrize("fault", [altered, half_left_out, misrouted],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(small_bench, capsys, fault):
    res = _run(small_bench, capsys, CELL, fault=_fault(fault))
    assert res["correct"] is False, res["checked"]


def test_benchmark_entries():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cfg = {c["name"]: c for c in spec["configs"]}["har_svm_rbf_auto16"]
    assert cfg["reduced"] == [] and os.path.exists(
        os.path.join(cells.ROOT, cfg["file"]))
    w = {c["name"]: c for c in spec["workloads"]}[CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "har_svm_rbf_auto16", "bulk", 1)
