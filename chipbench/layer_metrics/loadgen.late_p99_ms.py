"""99th percentile of how late the generator sent a query against its
schedule (ms).  A starved generator must not read as a fast server."""
from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx["counters"]["late_ms"], 99)
