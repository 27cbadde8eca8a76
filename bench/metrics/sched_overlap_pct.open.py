"""Share of the window's ``repro.batch.dispatch`` spans that launched
their batch while another batch's outputs were still unforced
(``overlapped`` 1).  A program whose dispatch spans carry no
``overlapped`` gives None."""

from bench.metrics._spans import records


def read(ctx):
    flags = [dict(r[6:]).get("overlapped") for r in records(ctx) or ()
             if r[0] == "repro.batch.dispatch"]
    flags = [f for f in flags if f is not None]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
