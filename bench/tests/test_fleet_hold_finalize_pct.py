"""The ``fleet_hold_finalize_pct`` reader on hand-made span records, on
the records of a program that records none, and through ``read(ctx)``."""

import os
import sys

import pytest

from bench import cells

THREAD = 11


def rec(name, start, end, id=0, parent=0, **ints):
    return (name, start, end, THREAD, id, parent) + tuple(ints.items())


def records():
    """Four finalizes that say whether they ran in a hold (the third did
    not), one that does not say, and spans of other kinds beside them."""
    held = [rec("repro.fleet.finalize", 0.001 * i, 0.001 * i + 0.0005,
                id=40 + i, parent=30 + i, round=20 + i, in_hold=int(i != 2))
            for i in range(4)]
    return held + [
        rec("repro.fleet.finalize", 0.0061, 0.0062, id=50, round=49),
        rec("repro.fleet.hold", 0.0005, 0.0009, id=60, parent=30),
        rec("repro.predict.sync", 0.0001, 0.0004, id=61, parent=40),
        rec("repro.fleet.round", 0.0, 0.001, id=30, riders=2, requests=2,
            bucket=1),
    ]


def _reader():
    return cells.Cell("trap_fleet32_auto16.zipf_b1").reader(
        "fleet_hold_finalize_pct")


def test_hold_finalize_share_counts_finalizes_run_in_a_hold(monkeypatch):
    from repro import spans

    recs = records()
    monkeypatch.setattr(spans, "collected", lambda t0, t1: [
        r for r in recs if t0 <= r[1] < t1])
    read = _reader()
    # Three of the four finalizes that say where they ran were in a hold.
    assert read({"window": {"t0": 0.0, "t1": 0.010}}) == pytest.approx(75.0)
    assert read({"window": {"t0": 0.0015, "t1": 0.010}}) == pytest.approx(
        50.0)
    # Finalizes that do not say (a program older than the attribute), or
    # none at all: nothing to report.
    assert read({"window": {"t0": 0.004, "t1": 0.010}}) is None
    assert read({"window": {"t0": 1.0, "t1": 2.0}}) is None


def test_hold_finalize_share_of_a_program_without_spans_is_none(
        monkeypatch):
    import repro

    monkeypatch.delattr(repro, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert _reader()({"window": {"t0": 0.0, "t1": 0.010}}) is None


def test_hold_finalize_share_is_listed_for_the_fleet_cell():
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in spec["per_layer"]}[
        "fleet_hold_finalize_pct"]
    assert listed["source"] == "program_span"
    assert listed["layer"] == "fleet coalescer"
    assert listed["moves"] == "p50_ms"
    assert listed["workloads"] == ["trap_fleet32_auto16.zipf_b1"]
