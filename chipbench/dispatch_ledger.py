"""What the three ``driver.*`` readers of the dispatch pipeline share: the
program's own books on the dispatches it has in flight
(``flink_parameter_server_tpu/training/metrics.InFlight``), read from outside.

The program polls, right after a dispatch's jitted call returns, which of the
dispatches before it have their outputs ready, and puts two numbers on the
span the dispatch already has (``train.pull_compute_push``), as its ``args``:
``inflight``, the dispatches not yet known to be ready, this one included,
and ``ready_age_s``, dispatch to seen-ready of the newest one the poll just
dropped (read at the NEXT dispatch, so up to one dispatch interval high).
``ctx["spans"]`` holds the in-window spans.  A program without those books
(the parent) gives ``None`` everywhere and the result line leaves the metric
out; so does a run whose ring dropped spans (``SpanTracer.dropped`` above 0:
the window's head may be gone, and a median would read its tail).
"""
import sys
from typing import List, Optional


def dispatches(ctx) -> Optional[List[dict]]:
    """The window's dispatch spans that carry the books, oldest first, or
    ``None`` where there are none to read."""
    try:
        from flink_parameter_server_tpu.telemetry.spans import get_tracer
    except ImportError:
        return None
    dropped = getattr(get_tracer(), "dropped", 0)
    if dropped:
        print(
            f"[chipbench] the span ring dropped {dropped} spans in this run: "
            "the window's head may be gone, no driver.* pipeline metric is read",
            file=sys.stderr, flush=True,
        )
        return None
    found = [
        s for s in ctx["spans"]
        if s["name"] == "pull_compute_push" and s["component"] == "train"
        and s.get("args")
    ]
    return sorted(found, key=lambda s: s["start"]) or None


def arg_values(ctx, key: str) -> Optional[List[float]]:
    """``args[key]`` of the window's dispatches, where it is a number."""
    found = dispatches(ctx)
    if found is None:
        return None
    return [s["args"][key] for s in found if s["args"].get(key) is not None]
