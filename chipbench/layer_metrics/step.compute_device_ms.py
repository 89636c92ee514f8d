"""Device time a step of the ops under ``ps.compute`` and under no scope
inside it (ms): the logic's arithmetic, its state update left out
(``chipbench/program_trace.py``)."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.compute")
