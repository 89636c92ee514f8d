"""Device time a step of the ops under ``ps.dense_interact`` (ms): the join
of ``z0`` and the pulled rows to ``T``, the batched ``T T^t``, its entries
below the diagonal, and their backward pass (``models/dlrm.py``): the part
of the dense net whose products are 27 x 64, not a layer's.  Part of
``step.dense_device_ms``.  A program without that scope reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.dense_interact")
