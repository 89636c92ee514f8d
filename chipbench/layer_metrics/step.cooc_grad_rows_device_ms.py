"""Device time a step of the ops under ``ps.cooc_grad_rows`` (ms): where the
logic joins a record's two gradient rows in the server's row shape
(``models/glove.py``: ``(s w~_j, s, 0 x 301)`` and ``(s w_i, s, 0 x 301)``,
``(B, 2, 602)``, inside ``ps.compute``).  A program without that scope
reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.cooc_grad_rows")
