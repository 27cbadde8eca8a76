"""Finds a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell names a configuration (``configs/<config>.json``, whose ``kind``
names a module ``kinds/<kind>.py``) and a traffic mix
(``traffic/<traffic>.json``, whose ``generator`` names a module
``traffic/<generator>.py``); each metric is read by
``metrics/<metric name>.py``; each kernel's nominal work is
``work/<kernel>.py``.  Adding a cell, a mix, a metric or a kernel adds
files and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration, traffic
    mix and metrics, resolved from files under ``bench_dir``."""

    def __init__(self, workload: str, root: str = ROOT,
                 bench_dir: str = BENCH, spec: Optional[dict] = None):
        self.spec = spec if spec is not None else load_json(
            os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
        self.name = workload
        self.workload = cells[workload]
        self.bench_dir = bench_dir
        self.config = load_json(os.path.join(
            bench_dir, "configs", self.workload["config"] + ".json"))
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.workload["traffic"] + ".json"))
        self.generator = load_module(
            os.path.join(bench_dir, "traffic",
                         self.traffic["generator"] + ".py"),
            "bench_traffic_" + self.traffic["generator"])
        self.kind = load_module(
            os.path.join(bench_dir, "kinds", self.config["kind"] + ".py"),
            "bench_kind_" + self.config["kind"])

    def metrics(self, section: str) -> list:
        """The cell's metrics of ``end_to_end`` or ``per_layer``: those
        without a ``workloads`` key, and those that list this cell."""
        return [m for m in self.spec[section]
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_")).read


def work_modules(bench_dir: str = BENCH) -> dict:
    """Every ``work/<kernel>.py``, by file name: each gives ``KERNEL``,
    the kernel's name in the device trace, and its nominal ``work``."""
    d = os.path.join(bench_dir, "work")
    return {f[:-3]: load_module(os.path.join(d, f), "bench_work_" + f[:-3])
            for f in sorted(os.listdir(d))
            if f.endswith(".py") and not f.startswith("_")}


def dataset_rows(config: dict) -> tuple:
    """All rows of the configuration's dataset (training then test), as
    float32, and the rows that calibrate a calibrated format."""
    from repro.data import load_dataset

    ds = load_dataset(config["dataset"])
    rows = np.ascontiguousarray(
        np.concatenate([ds.x_train, ds.x_test]).astype(np.float32))
    n_cal = int(config.get("calibration_rows", 0))
    return rows, (ds.x_train[:n_cal] if n_cal else None)
