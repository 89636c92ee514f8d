"""Median top-K latency from the instant a query was due (ms): the same
samples as the end-to-end ``query_p95_ms``.  It sits on the steep part of
the wait distribution, so it is noisier than the tail."""
from chipbench.stats import median


def read(ctx):
    return median(ctx["counters"]["query_latency_ms"])
