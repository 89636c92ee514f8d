"""Device time a step of the ops under ``ps.bag_pool`` (ms): a logic's
masked average of the rows of a ragged key bag, inside ``ps.compute``
(``FastTextSkipGram.step``: the select of the bag's live lanes out of the
pulled ``(B, max_bag, dim)`` rows, their sum and the division by the count).
A program without that scope reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.bag_pool")
