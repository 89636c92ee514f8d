"""Device time a step of the ops under ``ps.dense_interact`` (ms) where the
interaction is DCN v2's low-rank cross network (``models/dlrm_dcnv2.py``: the
join of ``z0`` and the pooled vectors to ``x0``, three layers ``x0 * (W (V
x) + b) + x`` of ``(B, 3456) x (3456, 512)`` and ``(B, 512) x (512, 3456)``
products, and their backward pass): ``step.interact_device_ms``'s reading,
by that metric's own reader (it lists cell 10, whose interaction is the
pairwise dots, and a list is not to be edited).  Part of
``step.dcn_dense_device_ms``.  A program without that scope reports
nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("step.interact_device_ms").read(ctx)
