"""Distinct rows a step's rule rewrote over the keys it was pushed (%) where
a record's keys are multi-hot bags, every field over its own rows: a table
of one held row takes its field's whole batch on that row and the long
tables repeat little (72.8 % distinct in closed form,
``families/dlrm_dcnv2.distinct_rows_per_step``), which is what the combine is
worth here.  ``store.rule_rows_share``'s reading, by that metric's own reader
(it lists cell 6, and a list is not to be edited): the gauges
``store_rule_rows`` / ``store_rule_keys``.  A program without them (an ``add``
store, the parent) reports nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("store.rule_rows_share").read(ctx)
