"""Device time a step of the ops under ``ps.state_pull`` and
``ps.state_push`` (ms): the worker state's own gather and scatter-add (MF's
user factors), which ``ps.compute`` holds (``chipbench/program_trace.py``)."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.state_pull", "ps.state_push")
