"""Device time a step of the ops under the scope ``ps.pull`` (ms): the
store's gather, whatever XLA names it (``chipbench/program_trace.py``)."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.pull")
