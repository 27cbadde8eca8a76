"""Host time of the sync per dispatch: the mean ``repro.predict.sync``
span (the ``np.asarray`` that waits for the outputs and copies them to
the host)."""

from bench.metrics._spans import mean_ms, records


def read(ctx):
    return mean_ms(records(ctx), "repro.predict.sync")
