"""Spans of the serving path (repro.spans).

* off (no profiler trace): ``span`` hands back one shared no-op and
  nothing is recorded, while requests are served;
* on (under ``jax.profiler.trace``): a micro-batcher endpoint and a
  3-member fleet serving a few submits record every span of the serving
  path with correct parent links; every request's queue wait names the
  batch or round that took it; the profiler's own trace holds the same
  scoped spans; spans keep the real clock under an injected batcher clock;
  a calibrated rbf SVM endpoint's dispatch records the same program spans;
  a program call says whether its input crossed to the device flat;
* the ring keeps at most ``CAPACITY`` records and counts what it drops.
"""

import collections
import glob
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.compile import Target
from repro.models import train_kernel_svm, train_mlp
from repro.serve import BatchingPolicy, InferenceService, MicroBatcher

F, C = 8, 3
FLEET = ("m0", "m1", "m2")
SOLO = "solo"


def test_ring_keeps_its_bound_and_counts_what_it_drops(tmp_path):
    spans.clear()
    extra = 5
    with jax.profiler.trace(str(tmp_path)):
        for i in range(spans.CAPACITY + extra):
            spans.interval("repro.x", float(i), float(i) + 0.5, i=i)
        # Spans opened on other threads count too.
        th = threading.Thread(target=lambda: spans.span("repro.y").__enter__()
                              .__exit__(None, None, None))
        th.start()
        th.join()
    recs = spans.collected(float("-inf"), float("inf"))
    assert len(recs) == spans.CAPACITY
    assert recs[-1][0] == "repro.y"
    # Records of serving threads still alive from other tests may be
    # interleaved; the oldest records go first, whoever wrote them.
    mine = [_ints(r)["i"] for r in recs if r[0] == "repro.x"]
    others = len(recs) - len(mine) - 1
    assert mine == list(range(mine[0], spans.CAPACITY + extra))
    assert mine[0] == extra + 1 + others
    assert spans.dropped() >= mine[0]
    assert spans.collected(10.0 + mine[0], 12.0 + mine[0]) == [
        r for r in recs if r[0] == "repro.x"
        and 10.0 + mine[0] <= r[1] < 12.0 + mine[0]]
    spans.clear()
    assert spans.collected(float("-inf"), float("inf")) == []
    assert spans.dropped() == 0


def test_threads_racing_on_a_full_ring_lose_no_count(tmp_path, monkeypatch):
    """More recording threads than cores, switching as often as the
    interpreter allows, on a ring cut to 4096: every record is kept or
    counted as dropped, and span ids stay unique."""
    capacity = 4096
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=capacity))
    n_threads = (os.cpu_count() or 4) + 2
    per = -(-(capacity + 20000) // n_threads)
    start = threading.Barrier(n_threads)

    def record():
        start.wait(timeout=30)
        for i in range(per):
            if i % 8:
                spans.interval("repro.x", 0.0, 1.0)
            else:
                with spans.span("repro.y"):
                    pass

    spans.clear()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=record)
                       for _ in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
            assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    recs = spans.collected(float("-inf"), float("inf"))
    assert len(recs) == capacity
    # Other serving threads alive in the process can only add records.
    assert len(recs) + spans.dropped() >= n_threads * per
    assert len({r[4] for r in recs}) == len(recs)
    spans.clear()


@pytest.fixture(scope="module")
def rows():
    return np.random.RandomState(3).randn(64, F).astype(np.float32)


@pytest.fixture(scope="module")
def service(rows):
    """Three pallas MLPs stacked as a fleet, and one xla endpoint served by
    its own micro-batcher; every shape warmed before any trace."""
    y = np.arange(len(rows), dtype=np.int32) % C
    models = [train_mlp(rows, y, C, hidden=(8,), epochs=2, seed=s)
              for s in range(3)]
    policy = BatchingPolicy(max_batch=4, max_wait_ms=2)
    svc = InferenceService()
    for name, m in zip(FLEET, models):
        svc.register(name, m, Target(number_format="auto16",
                                     backend="pallas"),
                     policy=policy, calibration=rows)
    svc.register(SOLO, models[0], Target(number_format="fxp16",
                                         backend="xla"), policy=policy)
    formed = svc.enable_fleet(list(FLEET))
    assert [sorted(m) for m in formed.values()] == [list(FLEET)]
    for k in range(3):
        for f in [svc.submit(n, rows[k]) for n in FLEET + (SOLO,)]:
            f.result(timeout=120)
    yield svc
    svc.close()


def _serve(svc, rows, names, k):
    for f in [svc.submit(n, rows[k % len(rows)]) for n in names]:
        f.result(timeout=120)


@pytest.fixture(scope="module")
def traced(service, rows, tmp_path_factory):
    """One traced stretch of serving: the ring's records in it, the number
    of requests submitted, and the profiler's trace file."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    spans.clear()
    submitted = 0
    t0 = time.perf_counter()
    with jax.profiler.trace(log_dir):
        # Two of three members at once leave a partial stack, which the
        # coalescer holds for stragglers; repeat until one round held.
        for k in range(40):
            _serve(service, rows, ("m0", "m1", SOLO), k)
            submitted += 3
            held = [r for r in spans.collected(t0, float("inf"))
                    if r[0] == "repro.fleet.hold"]
            if k >= 5 and held:
                break
        # Bursts of two members' requests, more than one round takes,
        # leave a round in flight while the next one holds; repeat until
        # one round finalized it inside its hold and then slept.
        for k in range(40):
            futs = [service.submit(n, rows[i]) for i in range(8)
                    for n in ("m0", "m1")]
            for f in futs:
                f.result(timeout=120)
            submitted += len(futs)
            if _finalized_and_slept(spans.collected(t0, float("inf"))):
                break
        _serve(service, rows, FLEET, 0)
        submitted += 3
    # Spans open when the trace stopped (an idle round's sweep) close
    # within microseconds; none opens after it.
    time.sleep(0.2)
    t1 = time.perf_counter()
    recs = spans.collected(t0, t1)
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, "the profiler wrote no trace"
    return {"records": recs, "submitted": submitted, "xplane": paths[0],
            "t0": t0, "t1": t1}


def _finalized_and_slept(recs):
    """Rounds that finalized the round in flight inside their hold and
    then slept what was left of it."""
    slept = {r[5] for r in recs if r[0] == "repro.fleet.hold"}
    return {r[5] for r in recs if r[0] == "repro.fleet.finalize"
            and _ints(r)["in_hold"]} & slept


def _by_id(recs):
    return {r[4]: r for r in recs}


def _ints(rec):
    return dict(rec[6:])


def test_off_returns_the_shared_noop_and_records_nothing(service, rows):
    assert not spans.enabled()
    a, b = spans.span("repro.a"), spans.span("repro.b", rows=3)
    assert a is b and a.id == 0
    with a as sp:
        sp.set(rows=1)
    spans.clear()
    spans.interval("repro.c", 0.0, 1.0, k=1)
    _serve(service, rows, FLEET + (SOLO,), 5)
    assert spans.collected(float("-inf"), float("inf")) == []
    assert spans.dropped() == 0


def test_batcher_records_its_spans_with_parents(traced):
    recs = traced["records"]
    by_id = _by_id(recs)
    batches = {r[4]: r for r in recs
               if r[0] == "repro.batch" and r[5] == 0}
    assert batches
    kids = collections.defaultdict(list)
    for r in recs:
        if r[5] in batches:
            kids[r[5]].append(r[0])
    for bid, b in batches.items():
        assert sorted(kids[bid]) == ["repro.batch.assemble",
                                     "repro.batch.collect",
                                     "repro.batch.dispatch",
                                     "repro.batch.scatter"]
        ints = _ints(b)
        assert ints["requests"] >= 1 and ints["rows"] >= 1
        assert ints["bucket"] >= ints["rows"]
    # The program's call and sync sit inside the batch's dispatch.
    solo_calls = [r for r in recs if r[0].startswith("repro.predict.")
                  and by_id[r[5]][0] == "repro.batch.dispatch"]
    assert {r[0] for r in solo_calls} == {"repro.predict.call",
                                          "repro.predict.sync"}
    for r in recs:
        if r[5]:
            parent = by_id[r[5]]
            assert parent[3] == r[3]  # same thread
            assert parent[1] <= r[1] and r[2] <= parent[2]


def test_fleet_rounds_record_their_spans_with_parents(traced):
    recs = traced["records"]
    by_id = _by_id(recs)
    rounds = {r[4]: r for r in recs if r[0] == "repro.fleet.round"}
    assert all(r[5] == 0 for r in rounds.values())
    stacked = [r for r in rounds.values() if "bucket" in _ints(r)]
    assert stacked and all(_ints(r)["riders"] >= 2 for r in stacked)
    parent_of = collections.Counter(
        (r[0], by_id[r[5]][0]) for r in recs if r[5] in by_id)
    for child in ("collect", "hold", "assemble", "launch", "finalize"):
        assert parent_of[(f"repro.fleet.{child}", "repro.fleet.round")], child
    assert parent_of[("repro.predict.call", "repro.fleet.launch")]
    assert parent_of[("repro.predict.sync", "repro.fleet.finalize")]
    # Each finalize names the round that launched what it forces.
    for r in recs:
        if r[0] == "repro.fleet.finalize":
            assert _ints(r)["round"] in rounds
    # A round that holds first finalizes the round in flight, as a child
    # of its own, and then sleeps what is left of the hold: the finalize
    # ends before the hold starts, and no hold contains a finalize.
    finalizes = [r for r in recs if r[0] == "repro.fleet.finalize"]
    holds = [r for r in recs if r[0] == "repro.fleet.hold"]
    in_hold = [r for r in finalizes if _ints(r)["in_hold"] == 1]
    assert _finalized_and_slept(recs)
    held = {r[5] for r in holds} | {r[5] for r in in_hold}
    assert all(by_id[i][0] == "repro.fleet.round" for i in held)
    for f in finalizes:
        assert _ints(f)["in_hold"] == (f[5] in held)
        for h in holds:
            if h[5] == f[5]:
                assert f[2] <= h[1]
            assert not (h[1] <= f[1] and f[2] <= h[2])
    # The rounds that took this fleet's requests ran on one thread, the
    # coalescer's.
    took = {_ints(r)["taker"] for r in recs if r[0] == "repro.request.queue"}
    assert len({r[3] for i, r in rounds.items() if i in took}) == 1


def test_every_request_names_the_batch_or_round_that_took_it(traced):
    recs = traced["records"]
    by_id = _by_id(recs)
    waits = [r for r in recs if r[0] == "repro.request.queue"]
    assert len(waits) == traced["submitted"]
    assert len({_ints(r)["request"] for r in waits}) == len(waits)
    for r in waits:
        taker = by_id[_ints(r)["taker"]]
        assert taker[0] in ("repro.batch", "repro.fleet.round")
        assert r[1] <= r[2]
        # Taken while the taker was open.
        assert taker[1] <= r[2] <= taker[2]


def test_profiler_trace_holds_the_scoped_spans(traced):
    from jax.profiler import ProfileData

    names = collections.Counter()
    for plane in ProfileData.from_file(traced["xplane"]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    names[e.name] += 1
    ring = collections.Counter(r[0] for r in traced["records"]
                               if r[0] != "repro.request.queue")
    assert set(ring) <= set(names)
    assert "repro.request.queue" not in names  # memory only
    for name, n in ring.items():
        assert names[name] >= n, name


def test_svm_endpoint_dispatch_records_the_program_spans(rows, tmp_path):
    """A calibrated rbf SVM endpoint adds no host step of its own: its
    dispatch runs inside ``repro.batch`` > ``.dispatch`` >
    ``repro.predict.call`` / ``.sync``, like any endpoint's."""
    y = np.arange(len(rows), dtype=np.int32) % C
    model = train_kernel_svm(rows, y, C, kernel="rbf", n_prototypes=12,
                             epochs=2, seed=0)
    svc = InferenceService()
    try:
        svc.register("svm", model, Target(number_format="auto16",
                                          backend="pallas"),
                     policy=BatchingPolicy(max_batch=4, max_wait_ms=2),
                     calibration=rows)
        assert svc.endpoint("svm").artifact.kernel_strategy == "megakernel"
        svc.predict("svm", rows[:8])  # warm the bucket
        spans.clear()
        with jax.profiler.trace(str(tmp_path)):
            svc.predict("svm", rows[:8])
            svc.submit("svm", rows[0]).result(timeout=120)
        recs = spans.collected(float("-inf"), float("inf"))
    finally:
        svc.close()
    by_id = _by_id(recs)
    chain = collections.Counter(
        (r[0], by_id[r[5]][0], by_id[by_id[r[5]][5]][0]) for r in recs
        if r[0].startswith("repro.predict.") and r[5] in by_id)
    assert chain[("repro.predict.call", "repro.batch.dispatch",
                  "repro.batch")] >= 2
    assert chain[("repro.predict.sync", "repro.batch.dispatch",
                  "repro.batch")] >= 2


def test_program_call_says_whether_its_input_crossed_flat(rows, tmp_path):
    """A pallas solo endpoint's program takes its rows as one flat buffer,
    so its ``repro.predict.call`` carries ``flat`` 1; a ``ref`` artifact
    runs eagerly on the rows as given, ``flat`` 0."""
    y = np.arange(len(rows), dtype=np.int32) % C
    model = train_mlp(rows, y, C, hidden=(8,), epochs=2, seed=0)
    svc = InferenceService()
    try:
        for backend in ("pallas", "ref"):
            svc.register(backend, model, Target(number_format="fxp16",
                                                backend=backend),
                         policy=BatchingPolicy(max_batch=4, max_wait_ms=2))
            svc.predict(backend, rows[:4])  # warm the buckets
        spans.clear()
        with jax.profiler.trace(str(tmp_path)):
            for backend in ("pallas", "ref"):
                svc.submit(backend, rows[0]).result(timeout=120)
        recs = spans.collected(float("-inf"), float("inf"))
    finally:
        svc.close()
    calls = sorted((r for r in recs if r[0] == "repro.predict.call"),
                   key=lambda r: r[1])
    assert [_ints(r) for r in calls] == [{"rows": 1, "flat": 1},
                                         {"rows": 1, "flat": 0}]


def test_spans_keep_the_real_clock_under_an_injected_one(tmp_path):
    b = MicroBatcher(lambda x: x[:, 0], BatchingPolicy(max_batch=4),
                     clock=lambda: 1e9)
    try:
        spans.clear()
        with jax.profiler.trace(str(tmp_path)):
            t0 = time.perf_counter()
            b.submit(np.ones(3, np.float32)).result(timeout=30)
            t1 = time.perf_counter()
    finally:
        b.close()
    recs = spans.collected(float("-inf"), float("inf"))
    assert {r[0] for r in recs} >= {"repro.batch", "repro.request.queue"}
    assert all(t0 <= r[1] <= r[2] <= t1 for r in recs)


@pytest.fixture(scope="module")
def pipelined(service, rows, tmp_path_factory):
    """A traced bulk request of eight 64-row blocks on an xla endpoint,
    queued behind a first dispatch held by a delay fault, then three
    single-row requests: the records of the endpoint's worker thread."""
    from repro.serve import ModelRouter, faults
    from repro.serve.faults import FaultPlan, FaultRule

    art = service.endpoint(SOLO).artifact
    for b in (1, 64):
        art.predict(np.zeros((b, F), np.float32))
    router = ModelRouter()
    ep = router.register("bulk", art, policy=BatchingPolicy(
        max_batch=64, warmup=False))
    bulk = np.tile(rows, (8, 1))
    plan = FaultPlan([FaultRule(site="endpoint.dispatch", kind="delay",
                                delay_s=0.2, count=1)])
    try:
        spans.clear()
        with jax.profiler.trace(str(tmp_path_factory.mktemp("bulk"))):
            with faults.inject(plan):
                np.testing.assert_array_equal(ep.predict(bulk),
                                              art.predict(bulk))
            for k in range(3):
                ep.submit(rows[k]).result(timeout=60)
        worker = ep.batcher._worker.ident
    finally:
        router.close()
    return [r for r in spans.collected(float("-inf"), float("inf"))
            if r[3] == worker]


def test_batch_dispatch_spans_say_whether_they_overlapped(pipelined):
    by_id = _by_id(pipelined)
    flags = collections.defaultdict(list)
    for r in pipelined:
        if r[0] == "repro.batch.dispatch":
            flags[_ints(by_id[r[5]])["rows"]].append(_ints(r)["overlapped"])
    # The first block of the request launches with nothing in flight; each
    # of the other seven launches behind the block before, still unforced.
    assert flags[64] == [0] + [1] * 7
    assert flags[1] == [0, 0, 0]
    assert set(flags) == {1, 64}


def test_pipelined_program_spans_nest_under_dispatch_under_batch(pipelined):
    by_id = _by_id(pipelined)
    batches = {r[4] for r in pipelined
               if r[0] == "repro.batch" and r[5] == 0}
    assert len(batches) == 11
    kids = collections.defaultdict(list)
    for r in pipelined:
        if r[5] in batches:
            kids[r[5]].append(r[0])
    for bid in batches:
        assert sorted(kids[bid]) == ["repro.batch.assemble",
                                     "repro.batch.collect",
                                     "repro.batch.dispatch",
                                     "repro.batch.scatter"]
    chain = collections.Counter(
        (r[0], by_id[r[5]][0], by_id[by_id[r[5]][5]][0]) for r in pipelined
        if r[0].startswith("repro.predict."))
    assert chain == {("repro.predict.call", "repro.batch.dispatch",
                      "repro.batch"): 11,
                     ("repro.predict.sync", "repro.batch.dispatch",
                      "repro.batch"): 11}
    for r in pipelined:
        if r[5]:
            parent = by_id[r[5]]
            assert parent[1] <= r[1] and r[2] <= parent[2]
