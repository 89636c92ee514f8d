"""Device time a step of the bags' two ends inside ``ps.compute`` (ms): the
ops under ``ps.bag_pool`` (``models/dlrm_dcnv2.py``: a field's bag is a fixed
run of an example's pulled rows, summed to one vector a field) and under
``ps.bag_grad_spread`` (a pooled vector's gradient handed to every row of
its bag, the 214 raw gradient rows an example that the step pushes).
``step.bag_pool_device_ms`` reads the first scope alone (fastText's masked
average has no second).  A program without either scope reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.bag_pool", "ps.bag_grad_spread")
