"""The worker-state update's share of its HBM roofline (%): the bytes of
state rows one worker's records must move (a row read for the gather, a row
read and a row written for the update: 3 x records a worker x dim x
itemsize), over the chip's peak HBM bandwidth, over the measured device time
under ``ps.state_pull`` + ``ps.state_push`` on the busiest chip.  HBM
bandwidth bounds it: a row's update is O(1) flops a byte."""
import numpy as np

from chipbench import program_trace


def read(ctx):
    cfg, peaks = ctx["cfg"], ctx["peaks"]
    ms = program_trace.scope_ms(ctx, "ps.state_pull", "ps.state_push")
    if not ms or not peaks:
        return None
    per_worker = cfg["batch"] / ctx["chips"]
    least_s = (
        3.0 * per_worker * cfg["dim"] * np.dtype(cfg["dtype"]).itemsize
        / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / (ms / 1e3)
