"""The features' pull's share of its HBM roofline (%): the bytes the pull
must move whatever implements it (every pulled row read once and written
once at its 128 float32 lanes:
``families/<family>.feature_pull_bytes_per_step``, a lower bound, so this
cannot pass 100), over the chip's peak HBM bandwidth, over the measured
device time under ``ps.pull`` + ``store.feat``
(``store.feature_pull_device_ms``'s reading, by that metric's reader).  A
family without that function, or a program without the label, reports
nothing."""
from chipbench import spec


def read(ctx):
    ms = spec.metric_reader("store.feature_pull_device_ms").read(ctx)
    law = getattr(
        spec.family(ctx["cfg"]["family"]), "feature_pull_bytes_per_step", None)
    if not ms or not ctx["peaks"] or law is None:
        return None
    least_s = law(ctx["cfg"]) / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
