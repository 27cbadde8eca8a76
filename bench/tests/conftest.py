"""The benchmark's own tests run on the CPU, at sizes a test run holds:
``python -m pytest bench/tests`` from the root of the checkout."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# Sizes a CPU run in interpret mode holds; every other value is the cell's.
SMALL_TRAFFIC = {
    "bulk": {"request_rows": 512, "distinct_requests": 2,
             "policy": {"max_batch": 256}},
    "zipf_b1": {"rate_per_s": 200.0, "policy": {"max_batch": 4}},
    "poisson_b1": {"rate_per_s": 400.0, "policy": {"max_batch": 4}},
}
SMALL_CONFIG = {"trap_fleet32_auto16": {"members": 3}}


@pytest.fixture(scope="session", autouse=True)
def _isolated_tune_cache(tmp_path_factory):
    from repro.kernels import tune

    os.environ["REPRO_TUNE_CACHE"] = str(
        tmp_path_factory.mktemp("tune") / "tune_cache.json")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    tune.clear_memory_cache()
    yield


@pytest.fixture(scope="session")
def small_bench(tmp_path_factory):
    """A copy of the benchmark's files with the cells cut to test size."""
    src = os.path.join(ROOT, "bench")
    dst = str(tmp_path_factory.mktemp("bench"))
    for sub in ("configs", "kinds", "traffic", "metrics", "work"):
        shutil.copytree(os.path.join(src, sub), os.path.join(dst, sub))
    for name, over in SMALL_TRAFFIC.items():
        _merge(os.path.join(dst, "traffic", name + ".json"), over)
    for name, over in SMALL_CONFIG.items():
        _merge(os.path.join(dst, "configs", name + ".json"), over)
    return dst


def _merge(path, over):
    with open(path) as f:
        d = json.load(f)
    for k, v in over.items():
        if isinstance(v, dict):
            d[k].update(v)
        else:
            d[k] = v
    with open(path, "w") as f:
        json.dump(d, f)
