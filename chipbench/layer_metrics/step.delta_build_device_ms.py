"""Device time a step of the ops under ``ps.delta_build`` (ms): a logic's
assembly of the deltas it pushes, one per pulled row, inside ``ps.compute``
(``SkipGramNS.step``: a zeroed ``(B, N + 2, 2, dim)`` block and three
writes into it; under the mean combiner also the count of each word's lanes
and the block's division by it).  ``step.compute_device_ms`` then holds the
logits and the gradients alone.  A program without that scope reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.delta_build")
