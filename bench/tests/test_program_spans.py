"""The ``program_span`` readers on hand-made span records, on the records
of a program that records none, and through ``read(ctx)``."""

import os
import sys

import pytest

from bench import cells
from bench.metrics import _spans as S

THREAD, OTHER = 11, 22


def rec(name, start, end, thread=THREAD, id=0, parent=0, **ints):
    return (name, start, end, thread, id, parent) + tuple(ints.items())


def fleet_records():
    """Two rounds on the coalescer thread, one held 2 ms; a round that
    took nothing; two queue waits; a call and a sync per round."""
    return [
        rec("repro.request.queue", 0.0, 0.004, thread=OTHER, id=1,
            request=1, taker=10),
        rec("repro.request.queue", 0.002, 0.003, thread=OTHER, id=2,
            request=2, taker=10),
        rec("repro.fleet.hold", 0.001, 0.003, id=11, parent=10),
        rec("repro.predict.call", 0.0035, 0.0040, id=12, parent=13),
        rec("repro.fleet.launch", 0.0034, 0.0041, id=13, parent=10),
        rec("repro.predict.sync", 0.0042, 0.0048, id=14, parent=15),
        rec("repro.fleet.finalize", 0.0042, 0.0050, id=15, parent=10),
        rec("repro.fleet.round", 0.0, 0.005, id=10, riders=2, requests=2,
            bucket=1),
        rec("repro.predict.call", 0.0061, 0.0064, id=21, parent=20),
        rec("repro.predict.sync", 0.0065, 0.0067, id=22, parent=20),
        rec("repro.fleet.round", 0.006, 0.007, id=20, riders=2, requests=3,
            bucket=2),
        rec("repro.fleet.round", 0.008, 0.0081, id=30, riders=0,
            requests=0),
    ]


def test_means_and_medians_by_name():
    recs = fleet_records()
    assert S.mean_ms(recs, "repro.predict.call") == pytest.approx(0.4)
    assert S.mean_ms(recs, "repro.predict.sync") == pytest.approx(0.4)
    assert S.median_ms(recs, "repro.request.queue") == pytest.approx(2.5)
    assert S.mean_ms(recs, "repro.batch.dispatch") is None
    assert S.median_ms([], "repro.request.queue") is None


def test_hold_per_round_counts_rounds_that_took_requests():
    recs = fleet_records()
    # 2 ms of hold over the two rounds that took requests.
    assert S.hold_per_round_ms(recs) == pytest.approx(1.0)
    no_rounds = [r for r in recs if r[0] != "repro.fleet.round"]
    assert S.hold_per_round_ms(no_rounds) is None


def test_busy_share_is_rounds_less_holds_on_the_busiest_thread():
    recs = fleet_records()
    # Rounds: 5 + 1 + 0.1 ms, less the 2 ms hold = 4.1 ms of 10 ms.
    assert S.busy_pct(recs, 0.0, 0.010) == pytest.approx(41.0)
    # Clipped to the window: [0.002, 0.0065) holds 3 + 0.5 ms of rounds
    # less 1 ms of hold.
    assert S.busy_pct(recs, 0.002, 0.0065) == pytest.approx(
        100 * 2.5e-3 / 4.5e-3)
    batches = [rec("repro.batch", 0.0, 0.004, thread=1, id=1),
               rec("repro.batch.dispatch", 0.001, 0.003, thread=1, id=2,
                   parent=1),
               rec("repro.batch", 0.0, 0.006, thread=2, id=3),
               # a member batch served inside a round is not counted twice
               rec("repro.batch", 0.001, 0.002, thread=2, id=4, parent=9)]
    assert S.busy_pct(batches, 0.0, 0.010) == pytest.approx(60.0)
    assert S.busy_pct([], 0.0, 0.010) is None
    assert S.busy_pct(batches[1:2], 0.0, 0.010) is None


@pytest.fixture()
def program_spans(monkeypatch):
    """``repro.spans`` giving the hand-made records of the window."""
    from repro import spans

    recs = fleet_records()
    monkeypatch.setattr(spans, "collected", lambda t0, t1: [
        r for r in recs if t0 <= r[1] < t1])
    return recs


READ = {"queue_wait_ms.open": 2.5, "fleet_hold_ms": 1.0,
        "worker_busy_pct.open": 41.0, "dispatch_call_ms.bulk": 0.4,
        "dispatch_call_ms.open": 0.4, "dispatch_sync_ms.bulk": 0.4,
        "dispatch_sync_ms.open": 0.4}


def _reader(name):
    return cells.Cell("trap_fleet32_auto16.zipf_b1").reader(name)


@pytest.mark.parametrize("name", sorted(READ))
def test_reader_reads_the_window(program_spans, name):
    ctx = {"window": {"t0": 0.0, "t1": 0.010}}
    assert _reader(name)(ctx) == pytest.approx(READ[name])
    # A window with no span of the kind: nothing to report.
    assert _reader(name)({"window": {"t0": 1.0, "t1": 2.0}}) is None


@pytest.mark.parametrize("name", sorted(READ))
def test_reader_of_a_program_without_spans_reports_nothing(
        monkeypatch, name):
    import repro

    monkeypatch.delattr(repro, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert _reader(name)({"window": {"t0": 0.0, "t1": 0.010}}) is None


def test_every_span_reader_is_listed_as_a_program_span():
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name in READ:
        assert listed[name]["source"] == "program_span"
        assert listed[name]["workloads"]
