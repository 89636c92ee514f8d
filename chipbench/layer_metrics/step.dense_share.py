"""The dense net's share of the step's device time (%):
``step.dense_device_ms``'s reading (by that metric's reader) over the median
device duration of the step program (``step.device_ms``'s).  What a
parameter server's step spends on the worker's own model, beside its pulls
and pushes.  A program without the dense net's scopes reports nothing."""
from chipbench import spec


def read(ctx):
    ms = spec.metric_reader("step.dense_device_ms").read(ctx)
    step = ctx["trace"] and ctx["trace"]["step_device_ms"]
    return 100.0 * ms / step if ms and step else None
