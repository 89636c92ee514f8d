"""Device time a step of the SERVER side of the step (ms) where a record's
keys are multi-hot bags and the rule's row is exactly two registers
(``models/dlrm_dcnv2.py``: 128 weights and Adagrad's 128 accumulators): the
ops under ``ps.combine`` + ``ps.rule`` + ``ps.push``, the sums of the batch's
gradient rows at the worker's 128 lanes, the read of the distinct rows whole
and the rule on them, the write-back; ``store.rule_path_device_ms``'s reading,
by that metric's own reader (it lists cell 9, and a list is not to be
edited).  A program without ``ps.combine`` (an ``add`` store, the parent)
reports nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("store.rule_path_device_ms").read(ctx)
