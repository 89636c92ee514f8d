"""Wide & Deep's dense net's share of the chip's matrix peak (%): the MODEL
floating-point operations of a step (``families/wdl.dense_flops_per_step``:
2 a multiply-add of the forward pass, the backward pass twice that; the
passes a float32 product takes on a bfloat16 MXU are not counted, so at
``Precision.HIGHEST``, six passes, this reads 17 at most), over the measured
device time under the net's scope, over the chip's published bfloat16 peak;
``step.dense_mxu_share``'s reading, by that metric's own reader (it lists
cell 10, and a list is not to be edited).  A family without that function,
or a program without the scope, reports nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("step.dense_mxu_share").read(ctx)
