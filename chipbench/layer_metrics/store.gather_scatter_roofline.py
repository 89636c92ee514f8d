"""The store's share of its HBM roofline (%): the bytes the step's gathers
and scatters must move (a function of shapes, ``families/<family>.py``),
per chip, over the chip's peak HBM bandwidth, over the measured device time
of the step.  HBM bandwidth bounds it: the step does O(1) flops a byte."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not peaks or not trace["step_device_ms"]:
        return None
    least_s = (
        ctx["counters"]["hbm_bytes_per_step"] / ctx["chips"]
        / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / (trace["step_device_ms"] / 1e3)
