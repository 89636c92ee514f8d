"""GraphSAGE's net's share of the chip's matrix peak (%): the MODEL
floating-point operations of a step (``families/sage.dense_flops_per_step``:
2 a multiply-add of the forward pass, the backward pass twice that less the
first layer's input gradient; the passes a float32 product takes on a
bfloat16 MXU are not counted, so at ``Precision.HIGHEST``, six passes, this
reads 17 at most), over the measured device time under the net's scopes
(``step.sage_dense_device_ms``'s reading, by that metric's reader), over the
chip's published bfloat16 peak.  A family without that function, or a
program without the scopes, reports nothing."""
from chipbench import spec


def read(ctx):
    ms = spec.metric_reader("step.sage_dense_device_ms").read(ctx)
    flops = getattr(
        spec.family(ctx["cfg"]["family"]), "dense_flops_per_step", None)
    if not ms or not ctx["peaks"] or flops is None:
        return None
    least_s = flops(ctx["cfg"]) / ctx["chips"] / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
