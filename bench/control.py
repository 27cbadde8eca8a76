#!/usr/bin/env python3
"""Readings that set a cell's limits: the program on many seeds and the
control (the configuration's next lower precision, served through the
same path) on a few, each a full window at the cell's own load, all in
one process so that the set-up is paid once per format.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3

Prints one ``reading`` line per run: the format, the seed and the
numbers compared with their limits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(run.ROOT, "src"), run.ROOT]
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from bench.cells import Cell

    if jax.devices()[0].platform != "tpu":
        print("FAIL: no TPU", file=sys.stderr)
        return 2
    cell = Cell(args.workload)
    plan = [(None, args.seeds),
            (cell.config["control"]["number_format"], args.control_seeds)]
    for fmt, seeds in plan:
        seeds = [int(s) for s in seeds.split(",")]
        bench = run.Bench(cell, seeds[0], number_format=fmt)
        windows = []
        for seed in seeds:
            bench.reseed(seed)
            windows.append((seed, bench.window(args.seconds)))
        bench.close()
        for seed, w in windows:
            checked = bench.check(w)
            print("reading " + json.dumps({
                "format": fmt or bench.target.number_format, "seed": seed,
                "attempted": w["attempted"], "rows": w["rows"],
                "checked": checked}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
