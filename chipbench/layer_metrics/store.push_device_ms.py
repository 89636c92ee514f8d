"""Device time a step of the ops under the scope ``ps.push`` (ms): the
store's scatter-add (``chipbench/program_trace.py``)."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.push")
