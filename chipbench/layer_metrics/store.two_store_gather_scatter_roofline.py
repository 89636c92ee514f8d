"""Two stores' share of their HBM roofline (%): the bytes both stores MUST
move a step whatever implements them (``families/wdl.hbm_bytes_per_step``:
a lower bound, so this cannot pass 100), over the chip's peak HBM bandwidth,
over the measured device time of the four store spans (each store's pull and
push: ``chipbench/store_trace.py``).  ``store.gather_scatter_roofline`` holds
the same bytes against the WHOLE step, the dense net's time included.  A
program without the stores' labels reports nothing."""
from chipbench import store_trace


def read(ctx):
    ms = store_trace.store_ms(
        ctx, "pull.wide", "push.wide", "pull.deep", "push.deep")
    if not ms or not ctx["peaks"]:
        return None
    least_s = (
        ctx["counters"]["hbm_bytes_per_step"] / ctx["chips"]
        / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
