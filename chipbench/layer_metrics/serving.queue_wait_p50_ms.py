"""Median time a served query spent in the admission queue (ms): the
program's ``serving.queue_wait`` records, admission stamp to popped."""
from chipbench import program_trace
from chipbench.stats import median


def read(ctx):
    return median(program_trace.span_ms(ctx, "serving", "queue_wait"))
