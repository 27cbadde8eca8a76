"""Reduces a profiler trace to the numbers the per-layer metrics read.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists, ``[{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns], ...]}]}]``, with every plane's events on one clock (ns from
the trace's start).  :func:`reduce` takes that form, so a test can feed
it a small recorded trace:

* the window: the host span ``bench.window`` (the harness opens it around
  the measured window), else the whole trace;
* device busy time: the union of the intervals in which an operation ran
  on each TPU device (the ``XLA Ops`` line), clipped to the window and
  averaged over the devices;
* kernel time: the summed durations and the count of the device
  operations whose HLO instruction name holds a kernel's name;
* the idle gaps between busy intervals, each put down to the host span
  (``bench.*``, from the harness's own calls) that overlaps it most, or
  to ``no host span``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "no host span"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> List[dict]:
    """The trace's planes, lines and events as plain lists."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals as disjoint sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float) -> Tuple[float, float]:
    return max(s, lo), min(e, hi)


def _host_spans(planes: Sequence[dict]) -> List[Tuple[str, float, float]]:
    spans = []
    for p in planes:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            for name, s, d in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, s, s + d))
    return spans


def op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fxp_mlp_model_pallas.1 = s16[...] custom-call(...)``); keep the
    instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def _device_ops(plane: dict) -> List[Tuple[str, float, float]]:
    for line in plane["lines"]:
        if line["name"] == OPS_LINE:
            return [(op_name(n), s, s + d) for n, s, d in line["events"]]
    return []


def attribute(gaps: Sequence[Tuple[float, float]],
              spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle ns per host span name: each gap goes whole to the span that
    overlaps it most (``NO_SPAN`` where none does)."""
    spans = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    # reach[i]: the latest end among spans[:i+1], so the first span that
    # can reach a gap is found by bisection.
    reach, r = [], float("-inf")
    for _, e, _ in spans:
        r = max(r, e)
        reach.append(r)
    out: Dict[str, float] = {}
    for gs, ge in gaps:
        best, best_ov = NO_SPAN, 0.0
        for i in range(bisect.bisect_right(reach, gs), len(spans)):
            s, e, n = spans[i]
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = n, ov
        out[best] = out.get(best, 0.0) + (ge - gs)
    return out


def reduce(planes: Sequence[dict], kernels: Sequence[str] = (),
           top: int = 10) -> Optional[dict]:
    """Busy and idle time, kernel time and the top device ops and idle
    causes of the traced window; None when no TPU device plane holds an
    operation."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    ops_by_dev = [ops for ops in (_device_ops(p) for p in devices) if ops]
    if not ops_by_dev:
        return None
    spans = _host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for ops in ops_by_dev for _, s, _ in ops)
        hi = max(e for ops in ops_by_dev for _, _, e in ops)
    busy, idle, op_time = [], {}, {}
    kernel = {k: {"time_s": 0.0, "calls": 0} for k in kernels}
    for i, ops in enumerate(ops_by_dev):
        inside = [(n,) + _clip(s, e, lo, hi) for n, s, e in ops]
        inside = [(n, s, e) for n, s, e in inside if e > s]
        merged = merge((s, e) for _, s, e in inside)
        busy.append(sum(e - s for s, e in merged))
        for n, s, e in inside:
            op_time[n] = op_time.get(n, 0.0) + (e - s)
            for k in kernels:
                if k in n:
                    kernel[k]["time_s"] += (e - s) / 1e9
                    kernel[k]["calls"] += 1
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
            idle = attribute(gaps, spans)
    n_dev = len(ops_by_dev)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "devices": n_dev,
        "kernels": {k: {"time_s": v["time_s"] / n_dev,
                        "calls": v["calls"] // n_dev}
                    for k, v in kernel.items()},
        "device_ops": [[n, t / n_dev / 1e9] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t / 1e9] for n, t in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }
