"""Device time a step of the ops under ``ps.delta_build`` (ms) where the
logic pushes raw GRADIENTS in the server's row shape (``models/difacto.py``:
the join of ``gw``, ``gV`` and the zero lanes of the optimiser's state into
``(B, K, 4 + 2 dim)`` rows, inside ``ps.compute``); ``step.delta_build_device_ms``'s
reading, which lists cell 5.  A program without that scope reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.delta_build")
