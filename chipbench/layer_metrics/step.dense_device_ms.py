"""Device time a step of the worker's dense net (ms): the ops under
``ps.dense_bottom`` + ``ps.dense_interact`` + ``ps.dense_top`` inside
``ps.compute``, forward and backward pass (``models/dlrm.py``: the bottom
MLP, the pairwise dots ``T T^t`` with their triangle, the top MLP with the
loss's gradient).  The SGD on the leaves (``ps.dense_sgd``) and the row
deltas (``ps.delta_build``) are left out.  A program without those scopes
(every logic without a dense net, the parent) reports nothing."""
from chipbench import program_trace

SCOPES = ("ps.dense_bottom", "ps.dense_interact", "ps.dense_top")


def read(ctx):
    return program_trace.scope_ms(ctx, *SCOPES)
