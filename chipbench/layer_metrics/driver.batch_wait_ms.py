"""Median time the training thread was blocked on the prefetch queue for
its next batch (ms): the program's span ``train.batch_wait``, one a batch."""
from chipbench import program_trace
from chipbench.stats import median


def read(ctx):
    return median(program_trace.span_ms(ctx, "train", "batch_wait"))
