"""Device time a step of the ``deep`` store's pull in a step over two
stores (ms): the ops labelled ``store.deep`` under ``ps.pull``,
the gather (of a narrow rule store that reads a batch's distinct rows
once: its sorts, its fetch and the way back to the lanes)
(``chipbench/store_trace.py``).  A program without the label (every step
over one store, the parent) reports nothing."""
from chipbench import store_trace


def read(ctx):
    return store_trace.store_ms(ctx, "pull.deep")
