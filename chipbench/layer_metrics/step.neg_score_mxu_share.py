"""The chunked scoring's share of the chip's matrix peak (%): the MODEL
floating-point operations of a step's scores and their gradients
(``families/<family>.score_flops_per_step``: 2 a multiply-add of the forward
product, the backward pass twice that; the passes a float32 product takes on
a bfloat16 MXU are not counted, so at ``Precision.HIGHEST``, six passes, this
reads 17 at most, and products of 50 x 100 x 100 fill little of a 128 x 128
array), per chip, over the measured device time under ``ps.kge_score`` +
``ps.kge_score_grad`` (``step.neg_score_device_ms``'s reading, by that
metric's reader), over the chip's published bfloat16 peak, as
``step.dense_mxu_share`` counts.  A family without that function, or a program
without those scopes (the parent), reports nothing."""
from chipbench import spec


def read(ctx):
    ms = spec.metric_reader("step.neg_score_device_ms").read(ctx)
    flops = getattr(
        spec.family(ctx["cfg"]["family"]), "score_flops_per_step", None
    )
    if not ms or not ctx["peaks"] or flops is None:
        return None
    least_s = flops(ctx["cfg"]) / ctx["chips"] / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
