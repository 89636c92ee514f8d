"""Device time a step of GraphSAGE's net in the worker (ms): the ops under
``ps.sage_dense`` (``models/graphsage.GraphSage.step``: the children's means,
the three layers forward and backward, dropout's masks, the loss) and under
``ps.dense_adam`` (Adam on the leaves).  A program without those scopes
reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.sage_dense", "ps.dense_adam")
