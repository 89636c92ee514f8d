"""Peak device memory of the run, set-up included, over the table's
LOGICAL bytes (``vocab_size x 2 x dim x itemsize``, from the configuration):
how many tables the process held at its fullest.  A table that lies padded
on the chip reads a little over 1 alone (640 lanes for 600: 1.07); a copy
kept beside the one being trained adds one."""
import numpy as np


def read(ctx):
    cfg, peak = ctx["cfg"], ctx["counters"]["peak_hbm_bytes"]
    if not peak or not {"vocab_size", "dim", "dtype"} <= set(cfg):
        return None
    table = cfg["vocab_size"] * 2 * cfg["dim"] * np.dtype(cfg["dtype"]).itemsize
    return peak / table
