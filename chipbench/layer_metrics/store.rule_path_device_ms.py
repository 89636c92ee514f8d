"""Device time a step of the SERVER side of the step (ms): the ops under
``ps.combine`` + ``ps.rule`` + ``ps.push`` of a store whose update is a rule
and not ``add`` (``core/store._push_rule``): the sort of the batch's ids with
their gradients and the sums of each row's, the read of the distinct rows and
the rule on them, the write-back.  A program without ``ps.combine`` (an
``add`` store, the parent) reports nothing."""
from chipbench import program_trace


def read(ctx):
    if program_trace.scope_ms(ctx, "ps.combine") is None:
        return None
    return program_trace.scope_ms(ctx, "ps.combine", "ps.rule", "ps.push")
