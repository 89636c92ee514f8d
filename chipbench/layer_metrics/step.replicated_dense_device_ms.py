"""Device time a step of the worker's dense net (ms) where every chip of a
``ps`` mesh computes it WHOLE (one worker group, the MLPs replicated and
the batch too: cell 16): ``step.dense_device_ms``'s reading, by that
metric's own reader (it lists cell 10, and a list is not to be edited): the
ops under ``ps.dense_bottom`` + ``ps.dense_interact`` + ``ps.dense_top`` on
the busiest chip.  Split over the servers' own axis (the deployment's
data-parallel half: ROADMAP R21 (c)) it would be a quarter of this and the
dense gradients' all-reduce.  A program without those scopes reports
nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("step.dense_device_ms").read(ctx)
