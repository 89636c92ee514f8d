"""Device time a step of the ops under the scope ``ps.combine`` (ms): inside
the push of a store whose update is a rule and not ``add``, the sort of the
batch's ids with their deltas and the sums of each row's deltas
(``ops/dedup.combine_runs``).  A program without that scope (an ``add``
store, the parent) reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.combine")
