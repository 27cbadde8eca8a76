"""Host time of the program call per dispatch: the mean
``repro.predict.call`` span (the artifact's or the fleet stack's predict
call, input conversion and launch, up to its return)."""

from bench.metrics._spans import mean_ms, records


def read(ctx):
    return mean_ms(records(ctx), "repro.predict.call")
