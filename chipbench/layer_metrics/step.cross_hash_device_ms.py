"""Device time a step of the keys a logic COMPUTES in front of its pull
(ms): the ops under ``ps.cross_hash`` (``models/wide_deep.WideAndDeep.keys``:
the 851,968 crosses of two ids hashed to their buckets).  A program without
that scope reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.cross_hash")
