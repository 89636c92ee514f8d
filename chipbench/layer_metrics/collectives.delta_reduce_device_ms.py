"""Device time a step of the ops under ``ps.delta_reduce`` (ms), on the
busiest chip: the sum across the keyed workers of the item deltas each
scatter-added into its own zeroed table (``core/store.py``,
``_push_add_over_workers``: one all-reduce of a table-sized buffer).  An op
lasts from its chip reaching it until all have, so it holds the wait for the
slowest worker.  A program without the scope (one worker; the parent)
reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.delta_reduce")
