"""The ``sched_overlap_pct.bulk`` and ``sched_overlap_pct.open`` readers
on hand-made span records, on the records of a program whose dispatch
spans carry no ``overlapped``, and their entries in ``BENCHMARK.json``."""

import os
import sys

import pytest

from bench import cells

THREAD = 12
READERS = {"sched_overlap_pct.bulk": "har_mlp_fxp16.bulk",
           "sched_overlap_pct.open": "har_mlp_fxp16.poisson_b1"}


def rec(name, start, end, id=0, parent=0, **ints):
    return (name, start, end, THREAD, id, parent) + tuple(ints.items())


def records():
    """Eight dispatches of one bulk request (the first launched with
    nothing in flight), one dispatch that does not say, and spans of
    other kinds beside them."""
    out = []
    for i in range(8):
        t = 0.001 * i
        out += [rec("repro.batch", t, t + 0.0009, id=10 + i, requests=1,
                    rows=4096, bucket=4096),
                rec("repro.batch.dispatch", t + 0.0001, t + 0.0008,
                    id=20 + i, parent=10 + i, overlapped=int(i > 0)),
                rec("repro.predict.call", t + 0.0001, t + 0.0002, id=30 + i,
                    parent=20 + i, rows=4096)]
    return out + [rec("repro.batch.dispatch", 0.0091, 0.0095, id=50,
                      parent=49)]


def _reader(metric):
    return cells.Cell(READERS[metric]).reader(metric)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_overlap_share_counts_dispatches_launched_behind_a_batch(
        monkeypatch, metric):
    from repro import spans

    recs = records()
    monkeypatch.setattr(spans, "collected", lambda t0, t1: [
        r for r in recs if t0 <= r[1] < t1])
    read = _reader(metric)
    # Seven of the eight dispatches that say were overlapped.
    assert read({"window": {"t0": 0.0, "t1": 0.010}}) == pytest.approx(87.5)
    # From the fifth block on, all four were.
    assert read({"window": {"t0": 0.004, "t1": 0.010}}) == pytest.approx(
        100.0)
    # Dispatches that do not say (a program older than the int), or none
    # at all: nothing to report.
    assert read({"window": {"t0": 0.009, "t1": 0.010}}) is None
    assert read({"window": {"t0": 1.0, "t1": 2.0}}) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_overlap_share_of_a_program_without_spans_is_none(monkeypatch,
                                                          metric):
    import repro

    monkeypatch.delattr(repro, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert _reader(metric)({"window": {"t0": 0.0, "t1": 0.010}}) is None


def test_overlap_shares_are_listed_for_the_scheduler_cells():
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in spec["per_layer"]}
    bulk, open_ = (listed["sched_overlap_pct.bulk"],
                   listed["sched_overlap_pct.open"])
    for m in (bulk, open_):
        assert m["source"] == "program_span"
        assert m["layer"] == "scheduler"
        assert m["unit"] == "%" and m["better"] == "higher"
    assert bulk["moves"] == "rows_per_s"
    assert bulk["workloads"] == ["har_mlp_fxp16.bulk",
                                 "har_svm_rbf_auto16.bulk"]
    assert open_["moves"] == "p95_ms"
    assert open_["workloads"] == ["har_mlp_fxp16.poisson_b1"]
