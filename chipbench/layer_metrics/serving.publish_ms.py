"""Median snapshot publish (ms): the driver's own ``publish`` span, over
the publishes that copied (an offer the cadence declines takes microseconds
and is left out)."""
from chipbench.stats import median


def read(ctx):
    took = [s["dur"] * 1e3 for s in ctx["spans"] if s["name"] == "publish"]
    copies = [ms for ms in took if ms > 1.0]
    return median(copies)
