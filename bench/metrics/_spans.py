"""Shared reading of the program's own spans (``repro.spans``) for the
``program_span`` readers.

A record is ``(name, start, end, thread, id, parent, (key, value), ...)``
with ``perf_counter`` times, the clock of the window's ``t0`` and ``t1``.
A program that records no spans (one older than ``repro.spans``) gives
None, and so does a window with no span of the kind read.
"""

import statistics


def records(ctx):
    """The program's records that start inside the window, or None."""
    try:
        from repro import spans
    except ImportError:
        return None
    w = ctx["window"]
    return spans.collected(w["t0"], w["t1"])


def durations_ms(recs, name):
    return [1e3 * (r[2] - r[1]) for r in recs or () if r[0] == name]


def mean_ms(recs, name):
    d = durations_ms(recs, name)
    return statistics.fmean(d) if d else None


def median_ms(recs, name):
    d = durations_ms(recs, name)
    return statistics.median(d) if d else None


def hold_per_round_ms(recs):
    """Straggler hold per fleet round that took requests."""
    rounds = [r for r in recs or () if r[0] == "repro.fleet.round"
              and dict(r[6:]).get("requests", 0) > 0]
    if not rounds:
        return None
    return sum(durations_ms(recs, "repro.fleet.hold")) / len(rounds)


def busy_pct(recs, t0, t1):
    """Share of ``[t0, t1)`` in which a serving thread is inside a batch
    or a fleet round and not holding for stragglers; the busiest
    thread's share."""
    busy = {}
    for r in recs or ():
        top = r[0] in ("repro.batch", "repro.fleet.round") and r[5] == 0
        if not top and r[0] != "repro.fleet.hold":
            continue
        d = max(0.0, min(r[2], t1) - max(r[1], t0))
        busy[r[3]] = busy.get(r[3], 0.0) + (d if top else -d)
    if not busy or t1 <= t0:
        return None
    return 100.0 * max(busy.values()) / (t1 - t0)
