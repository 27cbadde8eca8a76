"""What decides ``correct``: a whole run of each cell, cut to test size,
with the chip check skipped.  A sound run is correct; the control (the
configuration's next lower precision served in its place) and each fault
planted under the timed path are not."""

import json

import numpy as np
import pytest

from bench import cells, run

CELLS = ("har_mlp_fxp16.bulk", "trap_fleet32_auto16.zipf_b1",
         "har_mlp_fxp16.poisson_b1")


def _run(small_bench, capsys, workload, **kw):
    cell = cells.Cell(workload, bench_dir=small_bench)
    rc = run.main(["--workload", workload, "--seed", "3000000019",
                   "--seconds", "1"], require_tpu=False, cell=cell, **kw)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    res["counters"] = json.loads(next(
        ln for ln in lines if ln.startswith("counters: "))[len("counters: "):])
    return res


def _wrap_artifacts(bench, change):
    """Break every endpoint's own program: its (M,) classes pass through
    ``change(classes, n_classes)``."""
    n = bench.cell.config["widths"][-1]
    for name in bench.names:
        art = bench.svc.endpoint(name).artifact
        inner = art._predict

        def predict(x, inner=inner):
            classes, stats = inner(x)
            return change(np.asarray(classes), n), stats

        art._predict = predict


def _wrap_fleet(bench, change):
    """Break the stacked program: its (E, M) classes pass through
    ``change`` too."""
    n = bench.cell.config["widths"][-1]
    for co in bench.svc._fleets.values():
        inner = co.stack._predict_device

        def predict(x, inner=inner):
            return change(np.asarray(inner(x)), n)

        co.stack._predict_device = predict


def altered(y, n):
    """An answer altered where it is produced: the next class."""
    return (y + 1) % n


def half_left_out(y, n):
    """Half of each dispatch's rows never computed: they come back as
    class 0.  A lone row counts as the half left out."""
    y = y.copy()
    y[..., y.shape[-1] // 2:] = 0
    return y


def misrouted(y, n):
    """Answers scattered to the wrong rows of the bucket (padding rows
    included) and, stacked, to the wrong members: every axis rolled by
    one."""
    return np.roll(y, 1, axis=tuple(range(y.ndim)))


def _fault(change):
    def plant(bench):
        _wrap_artifacts(bench, change)
        if bench.fleet:
            _wrap_fleet(bench, change)
    return plant


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(small_bench, capsys, workload):
    res = _run(small_bench, capsys, workload)
    assert res["correct"] is True, res["checked"]
    assert res["failed"] == 0
    # The fleet's window rides stacked rounds, not only solo batches.
    assert res["counters"]["rounds"] > 0 or "fleet" not in workload
    res.pop("counters")
    assert list(res)[-1] == "checked"


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(small_bench, capsys, workload):
    cfg = cells.Cell(workload, bench_dir=small_bench).config
    res = _run(small_bench, capsys, workload,
               number_format=cfg["control"]["number_format"])
    assert res["correct"] is False, res["checked"]


@pytest.mark.parametrize("fault", [altered, half_left_out, misrouted],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(small_bench, capsys, workload, fault):
    res = _run(small_bench, capsys, workload, fault=_fault(fault))
    assert res["correct"] is False, res["checked"]
