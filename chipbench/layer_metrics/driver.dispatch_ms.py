"""Median host time inside one dispatch (ms): the program's span
``train.pull_compute_push`` round the batch's ``device_put`` and the jitted
call, which includes the time the runtime's cap on programs in flight
holds the host."""
from chipbench import program_trace
from chipbench.stats import median


def read(ctx):
    return median(program_trace.span_ms(ctx, "train", "pull_compute_push"))
