"""Device time a step of the ``deep`` store's push in a step over two
stores (ms): the ops labelled ``store.deep`` under ``ps.push``,
the combine, the rule and the write-back
(``chipbench/store_trace.py``).  A program without the label (every step
over one store, the parent) reports nothing."""
from chipbench import store_trace


def read(ctx):
    return store_trace.store_ms(ctx, "push.deep")
