"""The trace reduction on a small recorded trace and on hand-made cases:
the busy union with overlapping operations, kernel time summed by name,
and idle gaps put down to the host spans around them."""

import json
import os

import numpy as np
import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _planes(ops, spans):
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Modules", "events": []},
                       {"name": "XLA Ops", "events": ops}]},
            {"name": "/host:CPU",
             "lines": [{"name": "python", "events": spans}]}]


def test_busy_is_the_union_of_overlapping_ops():
    ops = [["fusion.1", 100, 50], ["fxp_mlp_model_x", 120, 60],
           ["copy", 400, 100]]
    spans = [["bench.window", 100, 900]]
    r = T.reduce(_planes(ops, spans), kernels=("fxp_mlp_model",))
    assert r["window_s"] == pytest.approx(900e-9)
    # [100, 180) and [400, 500): 180 ns busy
    assert r["busy_s"] == pytest.approx(180e-9)
    assert r["kernels"]["fxp_mlp_model"] == {"time_s": pytest.approx(60e-9),
                                             "calls": 1}


def test_kernel_time_sums_every_call_by_name_and_clips_to_window():
    ops = [["a.fxp_mlp_fleet.1", 0, 100], ["b.fxp_mlp_fleet.2", 150, 100],
           ["fxp_mlp_fleet", 950, 100], ["other", 300, 10]]
    spans = [["bench.window", 50, 950]]
    r = T.reduce(_planes(ops, spans), kernels=("fxp_mlp_fleet", "absent"))
    k = r["kernels"]["fxp_mlp_fleet"]
    # 50 + 100 + 50 ns inside the window, three calls
    assert k["time_s"] == pytest.approx(200e-9) and k["calls"] == 3
    assert r["kernels"]["absent"] == {"time_s": 0.0, "calls": 0}
    assert r["device_ops"][0][0] in ("b.fxp_mlp_fleet.2",)


def test_gaps_go_to_the_host_span_that_overlaps_most():
    ops = [["k", 0, 100], ["k", 300, 100], ["k", 1000, 100]]
    spans = [["bench.window", 0, 1100],
             ["bench.submit", 100, 150], ["bench.wait", 250, 60],
             ["bench.submit", 400, 20], ["bench.wait", 500, 400]]
    r = T.reduce(_planes(ops, spans))
    idle = dict(r["idle_gaps"])
    # gap [100, 300): submit overlaps 150 ns, wait 50 -> submit, 200 ns;
    # gap [400, 1000): wait overlaps 400 ns -> wait, 600 ns
    assert idle == {"bench.submit": pytest.approx(200e-9),
                    "bench.wait": pytest.approx(600e-9)}
    assert r["busy_s"] + sum(idle.values()) == pytest.approx(r["window_s"])


def test_gap_with_no_span_and_no_device():
    r = T.reduce(_planes([["k", 0, 10]], [["bench.window", 0, 50]]))
    assert dict(r["idle_gaps"]) == {T.NO_SPAN: pytest.approx(40e-9)}
    assert T.reduce([{"name": "/host:CPU", "lines": []}]) is None


def test_recorded_chip_trace():
    """A slice of a real chip trace, checked against a plain count over a
    nanosecond timeline."""
    with open(os.path.join(DATA, "trace_small.json")) as f:
        rec = json.load(f)
    r = T.reduce(rec["planes"], kernels=rec["kernels"])
    ops = rec["planes"][0]["lines"][0]["events"]
    spans = rec["planes"][1]["lines"][0]["events"]
    lo = int(min(s for _, s, _ in ops))
    hi = int(max(s + d for _, s, d in ops))
    busy = np.zeros(hi - lo, bool)
    for _, s, d in ops:
        busy[int(s) - lo:int(s + d) - lo] = True
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["busy_s"] == pytest.approx(busy.sum() / 1e9)
    for k in rec["kernels"]:
        mine = [d for n, _, d in ops if k in T.op_name(n)]
        assert r["kernels"][k]["calls"] == len(mine) > 0
        assert r["kernels"][k]["time_s"] == pytest.approx(sum(mine) / 1e9)
    # Each idle run of the timeline goes to the span overlapping it most.
    edges = np.flatnonzero(np.diff(np.r_[True, busy, True].astype(int)))
    want = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        ov = {}
        for n, s, d in spans:
            o = min(s + d - lo, b) - max(s - lo, a)
            if o > ov.get(n, 0):
                ov[n] = o
        name = max(ov, key=ov.get) if ov else T.NO_SPAN
        want[name] = want.get(name, 0) + (b - a) / 1e9
    assert dict(r["idle_gaps"]) == pytest.approx(want)
    # The op names are the HLO instructions' names, not their text.
    assert all(" = " not in n for n, _ in r["device_ops"])


def test_load_reads_the_profilers_file(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    planes = T.load(T.find_xplane(str(tmp_path)))
    spans = [e for p in planes for ln in p["lines"] for e in ln["events"]
             if e[0] == "bench.window"]
    assert len(spans) == 1 and spans[0][2] > 0
