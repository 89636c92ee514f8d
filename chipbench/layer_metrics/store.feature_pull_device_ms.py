"""Device time a step of the features' pull (ms): the ops labelled
``store.feat`` under ``ps.pull`` (``chipbench/store_trace.py``), the one
gather of the 128-lane rows of every node a step met (806,000 at 1,000 seeds
and fan-outs 15 / 10 / 5), its keys a function of the rows the rounds before
it pulled.  A program without the label reports nothing."""
from chipbench import store_trace


def read(ctx):
    return store_trace.store_ms(ctx, "pull.feat")
