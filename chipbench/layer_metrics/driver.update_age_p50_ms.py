"""Median age of an update when the program sees it landed (ms): from the
return of a dispatch's jitted call to the poll that first finds its outputs
ready (``args["ready_age_s"]`` of the window's ``train.pull_compute_push``
spans).  The poll runs at the next dispatch, so this reads up to one dispatch
interval above the age ``pull_push_p50_ms`` times from outside."""
from chipbench import dispatch_ledger
from chipbench.stats import median


def read(ctx):
    values = dispatch_ledger.arg_values(ctx, "ready_age_s")
    return median(values) * 1e3 if values else None
