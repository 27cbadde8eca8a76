"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (assignment contract) and
writes JSON rows under benchmarks/results/.

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --quick    # 2 datasets, fast
  PYTHONPATH=src python -m benchmarks.run --smoke    # quick sizes, plus one
                                                     # consolidated
                                                     # BENCH_<name>.json per
                                                     # module at the repo root
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

warnings.filterwarnings("ignore")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="2 datasets only")
    ap.add_argument("--smoke", action="store_true",
                    help="quick sizes, plus a consolidated BENCH_<name>.json "
                         "per module at the repo root (the CI artifact set)")
    ap.add_argument("--only", default=None, help="comma-separated module list")
    args = ap.parse_args(argv)
    quick = args.quick or args.smoke

    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    from . import (compile_backends, emit_footprint, fig3_4_time,
                   fig5_6_memory, fig7_8_modifications, kernels_bench,
                   lm_quantized, megakernel, quant_accuracy, roofline_table,
                   serve_chaos, serve_fleet, serve_http, serve_sharded,
                   serve_throughput, table_v_accuracy, table_vi_vii_sigmoid,
                   table_viii_tools)
    from .common import RESULTS_DIR

    datasets = ("D5", "D2") if quick else None
    modules = {
        "table_v": lambda: table_v_accuracy.run(datasets or table_v_accuracy.DATASETS),
        "table_vi_vii": lambda: table_vi_vii_sigmoid.run(datasets or table_vi_vii_sigmoid.DATASETS),
        "fig3_4": lambda: fig3_4_time.run(datasets or fig3_4_time.DATASETS),
        "fig5_6": lambda: fig5_6_memory.run(datasets or fig5_6_memory.DATASETS),
        "fig7_8": lambda: fig7_8_modifications.run(datasets or fig7_8_modifications.DATASETS),
        "table_viii": lambda: table_viii_tools.run(datasets or table_viii_tools.DATASETS),
        "backends": lambda: compile_backends.run(
            ("D5",) if quick else compile_backends.DATASETS),
        "lm_quantized": lm_quantized.run,
        "kernels": kernels_bench.run,
        "megakernel": lambda: megakernel.run(smoke=quick)["rows"],
        "roofline": roofline_table.run,
        "serve": lambda: serve_throughput.run(smoke=quick)["rows"],
        "serve_sharded": lambda: serve_sharded.run(smoke=quick)["rows"],
        "serve_http": lambda: serve_http.run(smoke=quick)["rows"],
        "serve_fleet": lambda: serve_fleet.run(smoke=quick)["rows"],
        "chaos": lambda: serve_chaos.run(smoke=quick)["rows"],
        "quant": lambda: quant_accuracy.run(smoke=quick),
        "emit_footprint": lambda: emit_footprint.run(smoke=quick)["rows"],
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    os.makedirs(RESULTS_DIR, exist_ok=True)
    # --smoke additionally drops one consolidated BENCH_<name>.json per
    # module at the repo root — a flat, discoverable artifact set for CI
    # uploads (benchmarks/results/ stays the harness's own record).
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    failures = []
    for name, fn in modules.items():
        print(f"# === {name} ===")
        t0 = time.time()
        try:
            rows = fn()
            with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
                json.dump(rows, f, indent=1, default=str)
            if args.smoke:
                bench = {"benchmark": name, "smoke": True,
                         "elapsed_s": time.time() - t0, "rows": rows}
                path = os.path.join(repo_root, f"BENCH_{name}.json")
                with open(path, "w") as f:
                    json.dump(bench, f, indent=1, default=str)
            print(f"# {name}: {len(rows)} rows in {time.time() - t0:.1f}s")
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            import traceback
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all benchmarks OK")


if __name__ == "__main__":
    main()
