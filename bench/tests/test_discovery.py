"""A configuration, a traffic mix and a metric added as files are found
by their names, with no file of the harness edited."""

import json
import os
import shutil

from bench import cells

METRIC = '''
def read(ctx):
    return ctx["counters"]["rows"] * 2.0
'''

WORK = '''
KERNEL = "toy"


def work(widths, bits, rows, calls):
    return rows, rows
'''


def test_new_cell_found_by_name(tmp_path):
    src = cells.BENCH
    for sub in ("configs", "kinds", "traffic", "metrics", "work"):
        shutil.copytree(os.path.join(src, sub), tmp_path / sub)
    cfg = cells.load_json(os.path.join(src, "configs", "har_mlp_fxp16.json"))
    cfg["name"] = "d5_mlp_fxp16"
    cfg["dataset"], cfg["widths"] = "D5", [8, 64, 10]
    (tmp_path / "configs" / "d5_mlp_fxp16.json").write_text(json.dumps(cfg))
    mix = {"generator": "poisson_zipf", "rate_per_s": 10.0, "zipf_s": 0.0,
           "request_rows": 1, "policy": {"max_batch": 8, "max_wait_ms": 1,
                                         "eager_when_idle": False}}
    (tmp_path / "traffic" / "slow_b1.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "rows_twice.py").write_text(METRIC)
    (tmp_path / "work" / "toy_kernel.py").write_text(WORK)
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    spec["workloads"].append({"name": "d5_mlp_fxp16.slow_b1",
                              "config": "d5_mlp_fxp16",
                              "traffic": "slow_b1", "chips": 1})
    spec["per_layer"].append({"name": "rows_twice", "unit": "rows",
                              "workloads": ["d5_mlp_fxp16.slow_b1"]})
    cell = cells.Cell("d5_mlp_fxp16.slow_b1", spec=spec,
                      bench_dir=str(tmp_path))
    assert cell.config["widths"] == [8, 64, 10]
    assert cell.traffic["rate_per_s"] == 10.0
    sched = cell.generator.schedule(cell.traffic, 1, 100, 1, 2.0)
    assert len(sched["due"]) == 20
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "rows_twice" in names and "mfu.bulk" not in names
    assert cell.reader("rows_twice")({"counters": {"rows": 21}}) == 42.0
    assert cell.kind.params(cell.config)[0][0].shape == (8, 64)
    work = cells.work_modules(str(tmp_path))
    assert work["toy_kernel"].KERNEL == "toy" and "mlp_model" in work
    # setup_s has no workloads key: every cell reports it.
    assert "setup_s" in [m["name"] for m in cell.metrics("end_to_end")]


def test_every_metric_of_the_benchmark_has_a_reader():
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for section in ("end_to_end", "per_layer"):
        for m in spec[section]:
            assert os.path.exists(os.path.join(
                cells.BENCH, "metrics", m["name"] + ".py")), m["name"]
    for w in spec["workloads"]:
        cell = cells.Cell(w["name"], spec=spec)
        assert cell.config["name"] == w["config"]
