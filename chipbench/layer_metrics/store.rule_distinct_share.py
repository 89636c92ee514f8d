"""Distinct rows a step's rule rewrote over the keys it was pushed (%): what
the push's combine is worth.  ``store.rule_rows_share``'s reading, by that
metric's own reader (it lists cell 6, and a list is not to be edited): the
gauges ``store_rule_rows`` / ``store_rule_keys`` that ``StreamingDriver``
sets from the last dispatch's outputs once the loop has ended (no fetch
inside the window).  A program without them (an ``add`` store, the parent)
reports nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("store.rule_rows_share").read(ctx)
