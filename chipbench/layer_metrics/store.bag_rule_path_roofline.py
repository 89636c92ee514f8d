"""The two-register rule path's share of its HBM roofline (%): the bytes the
server side of a step must move whatever implements it (the pushed gradients
read once at their 128 lanes, every distinct row read once and written once
at its 256: ``families/<family>.rule_path_bytes_per_step``, a lower bound, so
this cannot pass 100), over the chip's peak HBM bandwidth, over the measured
device time under ``ps.combine`` + ``ps.rule`` + ``ps.push``;
``store.rule_path_roofline``'s reading, by that metric's own reader (it lists
cell 9, and a list is not to be edited).  A family without that function, or a
program without ``ps.combine`` (the parent), reports nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("store.rule_path_roofline").read(ctx)
