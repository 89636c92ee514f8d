"""The rule path's share of its HBM roofline (%): the bytes the server side
of a step must move whatever implements it (the batch's gradients read once
at the lanes that can be other than zero, every distinct row read once and
written once at its whole width: ``families/<family>.rule_path_bytes_per_step``,
a lower bound, so this cannot pass 100), over the chip's peak HBM bandwidth,
over the measured device time under ``ps.combine`` + ``ps.rule`` + ``ps.push``
(``store.rule_path_device_ms``'s reading, by that metric's reader).  HBM
bandwidth bounds it: the rule is O(1) flops a byte.  A family without that
function, or a program without ``ps.combine`` (the parent), reports nothing."""
from chipbench import spec


def read(ctx):
    ms = spec.metric_reader("store.rule_path_device_ms").read(ctx)
    least = getattr(
        spec.family(ctx["cfg"]["family"]), "rule_path_bytes_per_step", None
    )
    if not ms or not ctx["peaks"] or least is None:
        return None
    least_s = least(ctx["cfg"]) / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
