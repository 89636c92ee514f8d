"""Distinct rows a step's rule rewrote over the keys it was pushed (%) where
a record is a chunk of graph edges with its uniform negatives: under the
uniform law nearly every key of a step is a row of its own (99.5 % in closed
form), so the combine saves nothing and the rule path's work is the batch's.
``store.rule_rows_share``'s reading, by that metric's own reader (it lists
cell 6, and a list is not to be edited): the gauges ``store_rule_rows`` /
``store_rule_keys``.  A program without them (an ``add`` store, the parent)
reports nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("store.rule_rows_share").read(ctx)
