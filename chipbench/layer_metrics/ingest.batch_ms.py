"""Median host time to hand one device-ready batch to the step (ms) — the
benchmark's own span around its source, one sample a batch."""
from chipbench.stats import median


def read(ctx):
    return median(ctx["counters"]["ingest_ms"])
