"""95th percentile of the time a served query spent in the admission queue
(ms): the program's ``serving.queue_wait`` records."""
from chipbench import program_trace
from chipbench.stats import percentile


def read(ctx):
    return percentile(program_trace.span_ms(ctx, "serving", "queue_wait"), 95)
