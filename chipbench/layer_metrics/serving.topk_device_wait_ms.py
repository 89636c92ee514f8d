"""Median time a served top-K query's batch waited for its results after
its jitted call returned (ms): the program's span ``serving.topk_ready`` —
the device's queue in front of the kernel, the kernel, the fetch — taken a
QUERY, not a batch: each ``serving.queue_wait`` record names the
``serving.topk`` span that served it, and so does that span's ``topk_ready``.
(A batch sent while the device's queue is short returns in a millisecond or
two with few queries in it; the batches that wait carry most of them.)"""
from chipbench.stats import median


def read(ctx):
    ready = {
        s["parent_id"]: s["dur"] * 1e3 for s in ctx["spans"]
        if s["name"] == "topk_ready" and s["component"] == "serving"
    }
    return median([
        ready[s["parent_id"]] for s in ctx["spans"]
        if s["name"] == "queue_wait" and s["parent_id"] in ready
    ])
