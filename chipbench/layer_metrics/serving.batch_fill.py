"""Mean fill of the query batches against their bucket (%), from
``ServingMetrics.batch_fill`` (its rolling window of 1,024 batches)."""


def read(ctx):
    fill = ctx["counters"]["batch_fill"]
    return None if fill is None else 100.0 * fill
