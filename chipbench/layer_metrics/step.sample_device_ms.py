"""Device time a step of the sampler inside the step (ms): the three hops'
adjacency pulls, the ops labelled ``store.off`` and ``store.nbr`` under
``ps.pull`` (``chipbench/store_trace.py``: the rounds' gathers of whole
physical rows and the pick of a scalar out of each), and the draws between
them under ``ps.sample`` (``models/graphsage.GraphSage.next_keys``: the
random words, the remainder by a node's degree, the dead lanes).  A program
without those names (every other family, the parent) reports nothing."""
from chipbench import program_trace, store_trace


def read(ctx):
    found = [
        ms for ms in (
            program_trace.scope_ms(ctx, "ps.sample"),
            store_trace.store_ms(ctx, "pull.off", "pull.nbr"),
        ) if ms is not None
    ]
    return sum(found) if found else None
