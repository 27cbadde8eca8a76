#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration in ``bench/configs/`` and a traffic mix in
``bench/traffic/``.  A run builds the configuration's endpoints through
``repro.serve.InferenceService``, warms the shapes the mix uses, and then
drives the service for ``--seconds``: a closed loop of bulk
``predict`` calls, or an open loop of ``submit`` calls on a schedule.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the profiler and reports its per-layer metrics, each read
by ``bench/metrics/<name>.py``.

Once the window has closed, every answer it returned is compared with a
plain numpy reference by the configuration's kind (``bench/kinds/``),
and the numbers compared are printed beside their limits: the last lines on standard error, and
the ``checked`` key, last in the result line.  The last line of standard
output is the result, one JSON object.  A run that finds no TPU, or fewer
chips than the cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Answers still outstanding this long after the window closes are missing.
LATE_S = 60.0


def out(msg: str) -> None:
    print(msg, flush=True)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (a missing answer is ``inf``)."""
    s = np.sort(np.asarray(values, np.float64))
    return float(s[max(0, math.ceil(q / 100.0 * len(s)) - 1)])


class CompileCounter:
    """Counts JAX traces and backend compiles, so that compiles inside
    the measured window show."""

    def __init__(self):
        import jax

        self.traces = self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def mark(self) -> tuple:
        return self.traces, self.compiles


class Bench:
    """A cell's service, built and warmed, ready to drive windows."""

    def __init__(self, cell, seed: int, number_format: str = None):
        from bench import cells
        from repro.compile import Target
        from repro.serve import BatchingPolicy, InferenceService

        self.cell = cell
        cfg, traffic = cell.config, cell.traffic
        self.rows, cal = cells.dataset_rows(cfg)
        tgt = dict(cfg["target"])
        if number_format:
            tgt["number_format"] = number_format
        self.target = Target(**tgt)
        pol = traffic["policy"]
        self.policy = BatchingPolicy(
            max_batch=int(pol["max_batch"]),
            max_wait_ms=float(pol["max_wait_ms"]),
            eager_when_idle=bool(pol["eager_when_idle"]), warmup=False)
        self.members = int(cfg["members"])
        self.params = [cell.kind.params(cfg, e) for e in range(self.members)]
        self.names = [f"{cfg['name']}/{e}" for e in range(self.members)]
        self.svc = InferenceService()
        for name, p in zip(self.names, self.params):
            self.svc.register(name, cell.kind.model(p), self.target,
                              policy=self.policy,
                              calibration=(cal if self.target.is_calibrated
                                           else None))
        self.fleet = self.members > 1
        if self.fleet:
            formed = self.svc.enable_fleet(self.names)
            members = [m for ms in formed.values() for m in ms]
            if len(formed) != 1 or len(members) != self.members:
                raise RuntimeError(f"expected one fleet of {self.members}, "
                                   f"got {formed}")
        self.closed = cell.traffic["generator"] == "closed_bulk"
        self.reseed(seed)
        self.warm()

    def reseed(self, seed: int) -> None:
        """Draw the traffic of another seed (the weights stay: they come
        from the configuration)."""
        self.seed = int(seed)
        if self.closed:
            sched = self.cell.generator.schedule(
                self.cell.traffic, self.seed, len(self.rows))
            self.requests = [np.ascontiguousarray(self.rows[idx])
                             for idx in sched["requests"]]
            self.request_rows = sched["requests"]
            self.order = sched["order"]

    # -- set-up ----------------------------------------------------------------
    def buckets(self) -> list:
        """The batch shapes this cell's traffic can dispatch."""
        if self.closed:
            n, top = int(self.cell.traffic["request_rows"]), self.policy.max_batch
            shapes = {self.policy.bucket_for(min(n, top))}
            if n % top:
                shapes.add(self.policy.bucket_for(n % top))
            return sorted(shapes)
        return list(self.policy.buckets())

    def warm(self) -> None:
        f = self.rows.shape[1]
        for name in self.names:
            art = self.svc.endpoint(name).artifact
            for b in self.buckets():
                art.predict(np.zeros((b, f), np.float32))
        if self.closed:
            self.svc.predict(self.names[0], self.requests[0])
            return
        # Through the service itself: batchers and, for a fleet, the
        # coalescer, whose first stacked round traces the stacked program
        # over the bucket ladder.
        for _ in range(50):
            futs = [self.svc.submit(n, self.rows[i % len(self.rows)])
                    for i, n in enumerate(self.names)]
            concurrent.futures.wait(futs, timeout=600)
            if not self.fleet or self.counters()["stacked_dispatches"] > 0:
                break
        else:
            raise RuntimeError("the fleet made no stacked dispatch in warm-up")

    def counters(self) -> dict:
        st = self.svc.stats()
        c = dict.fromkeys(
            ("requests", "rows", "batches", "coalesced_batches",
             "coalesced_rows", "failed_requests", "solo_device_s", "rounds",
             "stacked_dispatches", "stacked_requests", "solo_batches",
             "stack_fallbacks", "fleet_device_s"), 0)
        for name in self.names:
            ep = st[name]
            for k in ("requests", "rows", "batches", "coalesced_batches",
                      "coalesced_rows", "failed_requests"):
                c[k] += ep[k]
            c["solo_device_s"] += ep["device_s"]
        for fl in st.get("_fleets", []):
            for k in ("rounds", "stacked_dispatches", "stacked_requests",
                      "solo_batches", "stack_fallbacks"):
                c[k] += fl[k]
            c["fleet_device_s"] += fl["device_s"]
        return c

    # -- the window --------------------------------------------------------------
    def drive_closed(self, seconds: float, span) -> dict:
        served, ids, failed = [], [], 0
        k = 0
        t0 = time.perf_counter()
        while True:
            i = int(self.order[k % len(self.order)])
            with span("bench.predict"):
                try:
                    y = self.svc.predict(self.names[0], self.requests[i])
                    served.append(np.asarray(y).astype(np.int8))
                    ids.append(i)
                except Exception as e:  # counted, and fails the check
                    failed += 1
                    out(f"request {k} failed: {e!r}")
            k += 1
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        n = int(self.cell.traffic["request_rows"])
        return {"kind": "closed", "t0": t0, "t1": t1, "window_s": t1 - t0,
                "attempted": k, "failed": failed, "served": served,
                "ids": ids, "rows": n * len(served)}

    def drive_open(self, seconds: float, span, rate: float = None) -> dict:
        sched = self.cell.generator.schedule(
            self.cell.traffic, self.seed, len(self.rows), self.members,
            seconds, rate_per_s=rate)
        due, member, row = sched["due"], sched["member"], sched["row"]
        n = len(due)
        done = np.full(n, np.inf)
        sent = np.zeros(n)
        served = np.full(n, -1, np.int64)
        names, rows, clock = self.names, self.rows, time.perf_counter
        # The futures are not kept: each answer is read by its callback,
        # so the window leaves no pile of live objects for the collector.
        left, lock, all_done = [n], threading.Lock(), threading.Event()

        def finish(k, fut):
            t = clock()
            if fut.exception() is None:
                done[k] = t
                served[k] = int(np.asarray(fut.result())[0])
            with lock:
                left[0] -= 1
                if not left[0]:
                    all_done.set()

        t0 = clock() + 0.01
        for k in range(n):
            wait = t0 + due[k] - clock()
            if wait > 0:
                time.sleep(wait)
            sent[k] = clock()
            with span("bench.submit"):
                self.svc.submit(names[member[k]], rows[row[k, 0]]) \
                    .add_done_callback(functools.partial(finish, k))
        t_close = t0 + seconds
        with span("bench.wait"):
            all_done.wait(max(0.0, t_close + LATE_S - clock()))
        t1 = clock()
        ok = served >= 0
        done[~ok] = np.inf
        due_abs = t0 + due
        return {"kind": "open", "t0": t0, "t1": t1, "window_s": t1 - t0,
                "seconds": seconds, "attempted": n,
                "failed": int(np.sum(~ok)),
                "latency_s": done - due_abs, "late_s": sent - due_abs,
                "due_s": due,
                "completed_by_close": int(np.sum(done <= t_close)),
                "served": served, "member": member, "row": row[:, 0],
                "rows": int(np.sum(ok))}

    def window(self, seconds: float, trace_dir: str = None,
               rate: float = None) -> dict:
        import jax

        span = (jax.profiler.TraceAnnotation if trace_dir
                else (lambda name: contextlib.nullcontext()))
        # Set-up is over: what it left on the heap is moved out of the
        # collector's way, so that a full collection in the window scans
        # only what the window itself allocates.
        gc.collect()
        gc.freeze()
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        c0 = self.counters()
        pauses, t_gc = [], [0.0]

        def on_gc(phase, info):
            if phase == "start":
                t_gc[0] = time.perf_counter()
            else:
                pauses.append((info["generation"], t_gc[0],
                               time.perf_counter() - t_gc[0]))

        gc.callbacks.append(on_gc)
        try:
            with span("bench.window"):
                if self.closed:
                    w = self.drive_closed(seconds, span)
                else:
                    w = self.drive_open(seconds, span, rate)
        finally:
            gc.callbacks.remove(on_gc)
            if trace_dir:
                jax.profiler.stop_trace()
        c1 = self.counters()
        w["counters"] = {k: c1[k] - c0[k] for k in c0}
        w["gc"] = [(g, t - w["t0"], d) for g, t, d in pauses]
        return w

    def close(self) -> None:
        self.svc.close()
        self.svc = None
        gc.collect()

    # -- the check ---------------------------------------------------------------
    def check(self, w: dict) -> dict:
        """The numbers compared with the reference, each with its limit."""
        if w["kind"] == "closed":
            n_req = w["attempted"]
            missing = (n_req - len(w["served"])) * int(
                self.cell.traffic["request_rows"])
            served = (np.concatenate(w["served"]) if w["served"]
                      else np.zeros(0, np.int64))
            rows = (np.concatenate([self.request_rows[i] for i in w["ids"]])
                    if w["ids"] else np.zeros(0, np.int64))
            member = np.zeros(len(rows), np.int64)
        else:
            ok = w["served"] >= 0
            missing = int(np.sum(~ok))
            served, rows, member = (w["served"][ok], w["row"][ok],
                                    w["member"][ok])
        checked = {"rows_missing": (missing, 0)}
        checked.update(self.cell.kind.compare(
            self.cell.config, self.rows, self.params, served, rows, member))
        return checked


def host_report(w: dict) -> str:
    """The window's garbage collections and, in an open loop, the moments
    the generator ran latest: what the host did that no metric shows."""
    gens = [sum(1 for g, _, _ in w["gc"] if g == k) for k in range(3)]
    longest = max((d for _, _, d in w["gc"]), default=0.0)
    msg = f"gc collections by generation {gens}, longest {longest * 1e3} ms"
    if w["kind"] == "open":
        late = w["late_s"]
        worst = np.argsort(late)[::-1][:3]
        due = np.asarray(w["due_s"])
        msg += ("; generator latest at " + ", ".join(
            f"{due[k]:.3f} s ({late[k] * 1e3:.1f} ms late)" for k in worst))
        gc_at = [(round(t, 3), round(d * 1e3, 1)) for g, t, d in w["gc"]
                 if d > 0.005]
        msg += f"; collections over 5 ms (s, ms): {gc_at}"
    return msg


def context(bench, w: dict, setup_s: float, reduced, peak: dict,
            work: dict) -> dict:
    """What the metric readers read."""
    return {"cell": bench.cell.name, "config": bench.cell.config,
            "traffic": bench.cell.traffic, "window": w, "setup_s": setup_s,
            "counters": w["counters"], "trace": reduced, "peak": peak,
            "widths": bench.cell.config["widths"],
            "bits": bench.target.container_bits, "members": bench.members,
            "work": work}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, require_tpu: bool = True, number_format: str = None,
         fault=None, cell=None) -> int:
    """One run.  The keyword arguments are for the benchmark's own tests
    and controls: ``require_tpu=False`` skips the look for a chip,
    ``number_format`` serves another format (the control's),
    ``fault`` is given the built :class:`Bench` and breaks its timed path,
    ``cell`` replaces the cell found by name."""
    args = parse(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        from repro.jax_cache import enable_compile_cache
    except ImportError as e:
        print(f"FAIL: the repro package is not in this checkout "
              f"({ROOT}/src): {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax
    from bench import trace as T
    from bench.cells import Cell, work_modules
    from bench.peaks import peaks
    from repro.kernels import tune

    devices = jax.devices()
    dev = devices[0]
    cell = cell or Cell(args.workload)
    work = work_modules(cell.bench_dir)
    if require_tpu and dev.platform != "tpu":
        print(f"FAIL: JAX found {dev.platform!r} ({dev.device_kind}), not a "
              f"TPU; the benchmark has no fallback", file=sys.stderr)
        return 2
    if len(devices) < int(cell.workload["chips"]):
        print(f"FAIL: the cell needs {cell.workload['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    peak = peaks(dev.device_kind) if require_tpu else None
    counter = CompileCounter()
    out(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")
    bench = Bench(cell, args.seed, number_format=number_format)
    if fault is not None:
        fault(bench)
    out(f"set-up: endpoints {len(bench.names)} target "
        f"{bench.target.number_format}/{bench.target.backend} buckets "
        f"{bench.buckets()} route "
        f"{bench.svc.endpoint(bench.names[0]).artifact.kernel_strategy} "
        f"tuning_s {tune.tuning_seconds()} dropped_candidates "
        f"{len(tune.dropped_candidates())} compiles {counter.compiles} "
        f"compile_s {counter.compile_s}")
    out(f"tuned blocks: {json.dumps(tune.cache_snapshot(), sort_keys=True)}")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        marks = counter.mark()
        w = bench.window(args.seconds, trace_dir=trace_dir)
        setup_s = w["t0"] - T_PROCESS
        traces, compiles = (a - b for a, b in zip(counter.mark(), marks))
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        out(f"window: {w['window_s']} s, traces inside {traces}, compiles "
            f"inside {compiles}")
        if w["kind"] == "open":
            late = w["late_s"]
            out(f"load: due {w['attempted']} sent {w['attempted']} completed "
                f"{w['rows']} completed by close {w['completed_by_close']} "
                f"generator late p99 {percentile(late, 99)} s max "
                f"{float(np.max(late))} s")
        else:
            out(f"load: requests {w['attempted']} completed "
                f"{len(w['served'])} rows {w['rows']}")
        out(f"counters: {json.dumps(w['counters'], sort_keys=True)}")
        out(f"host: {host_report(w)}")
        bench.close()
        reduced = None
        if trace_dir:
            planes = T.load(T.find_xplane(trace_dir))
            reduced = T.reduce(planes, kernels=[m.KERNEL
                                                for m in work.values()])
        checked = bench.check(w)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = context(bench, w, setup_s, reduced, peak, work)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(section):
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = all(v <= lim for v, lim in checked.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    result = {"correct": correct, "attempted": w["attempted"],
              "failed": w["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checked"] = {k: {"value": v, "limit": lim}
                         for k, (v, lim) in checked.items()}
    for k, (v, lim) in checked.items():
        print(f"checked {k}: {v} limit {lim}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
