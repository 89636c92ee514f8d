"""Device time a step of the SERVER side of the step (ms) where the rule's
row is exactly one register (``models/kge.py``: 101 lanes, the embedding and
row-wise AdaGrad's one accumulator): the ops under ``ps.combine`` +
``ps.rule`` + ``ps.push``, the sums of the batch's gradient rows, the read of
the distinct rows and the rule on them, the write-back;
``store.rule_path_device_ms``'s reading, by that metric's own reader (it
lists cell 9, and a list is not to be edited).  A program without
``ps.combine`` (an ``add`` store, the parent) reports nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("store.rule_path_device_ms").read(ctx)
