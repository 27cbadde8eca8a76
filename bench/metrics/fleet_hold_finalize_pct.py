"""Share of the window's ``repro.fleet.finalize`` spans that ran inside
the next round's straggler hold (``in_hold`` 1).  A program whose
finalize spans carry no ``in_hold`` gives None."""

from bench.metrics._spans import records


def read(ctx):
    flags = [dict(r[6:]).get("in_hold") for r in records(ctx) or ()
             if r[0] == "repro.fleet.finalize"]
    flags = [f for f in flags if f is not None]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
