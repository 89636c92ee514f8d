"""Device time a step of the worker's dense net (ms) where it is Wide &
Deep's (``models/wide_deep.py``): the ops under ``ps.dense_top`` inside
``ps.compute``, forward and backward pass of the 845-1024-512-256-1 net with
the join of the pulled rows in front and the loss's gradient in the middle;
``step.dense_device_ms``'s reading, by that metric's own reader (it lists
cell 10, and a list is not to be edited).  AdaGrad on the leaves
(``ps.dense_adagrad``) and the deltas (``ps.delta_build``) are left out.  A
program without that scope reports nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("step.dense_device_ms").read(ctx)
