"""Device time a step of the relations' operators in the worker (ms): the ops
under ``ps.kge_operator`` (the gather of an edge's two operators from the
worker's state, the element-wise complex products that turn an endpoint and
turn the gradients back) + ``ps.kge_operator_update`` (the gradients summed
by relation and AdaGrad on the whole leaf) inside ``ps.compute``
(``models/kge.py``).  A program without those scopes reports nothing."""
from chipbench import program_trace

SCOPES = ("ps.kge_operator", "ps.kge_operator_update")


def read(ctx):
    return program_trace.scope_ms(ctx, *SCOPES)
