#!/usr/bin/env python3
"""Knee sweep of one open-loop cell: the same service driven at several
fixed rates, one window each, in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1000,2000,4000

For each rate it prints the requests due, those completed by the
window's close, the queue left at the close (due minus completed), the
generator's lateness and the latency percentiles.  The knee is the
highest rate at which the completions keep up with the requests due and
no queue is left at the close; a cell's mix runs at 4/5 of it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(run.ROOT, "src"), run.ROOT]
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from bench.cells import Cell

    if jax.devices()[0].platform != "tpu":
        print("FAIL: no TPU", file=sys.stderr)
        return 2
    bench = run.Bench(Cell(args.workload), args.seed)
    if bench.closed:
        print("FAIL: a closed-loop cell has no knee", file=sys.stderr)
        return 2
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        w = bench.window(args.seconds, rate=rate)
        lat = w["latency_s"]
        row = {"rate": rate, "due": w["attempted"],
               "completed_by_close": w["completed_by_close"],
               "queue_at_close": w["attempted"] - w["completed_by_close"],
               "failed": w["failed"],
               "late_p99_ms": 1e3 * run.percentile(w["late_s"], 99),
               "p50_ms": 1e3 * run.percentile(lat, 50),
               "p95_ms": 1e3 * run.percentile(lat, 95),
               "p99_ms": 1e3 * run.percentile(lat, 99),
               "requests_per_round": (
                   w["counters"]["stacked_requests"] / w["counters"]["rounds"]
                   if w["counters"]["rounds"] else None),
               "rows_per_batch": (w["counters"]["rows"]
                                  / max(1, w["counters"]["batches"]))}
        print("sweep " + json.dumps(row), flush=True)
        print("host " + run.host_report(w), flush=True)
        rows.append(row)
    bench.close()
    print(json.dumps({"workload": args.workload, "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
