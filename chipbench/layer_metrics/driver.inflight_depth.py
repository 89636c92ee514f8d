"""Median number of dispatches in flight (count): the program's own count,
at each dispatch, of the dispatches whose outputs are not yet known to be
ready, that one included (``args["inflight"]`` of the window's
``train.pull_compute_push`` spans).  Free-running it is the runtime's cap on
programs in flight, and ``pull_push_p50_ms`` is this many dispatch times."""
from chipbench import dispatch_ledger
from chipbench.stats import median


def read(ctx):
    values = dispatch_ledger.arg_values(ctx, "inflight")
    return median(values) if values else None
