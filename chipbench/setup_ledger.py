"""What the six ``setup.*`` readers share: the program's own books on its
set-up (``flink_parameter_server_tpu/telemetry/compile_ledger.py``), read
from outside.

The compile ledger keeps every trace, lowering, backend compile (a load from
the persistent cache is one) and cache miss as an event with its start on
the tracer's clock.  Set-up's are those that BEGAN before the
window's first in-window span (``ctx["spans"]`` holds in-window spans only):
what the harness itself compiles after the window (``_all_finite``, the
rows' read-back) stays out, and a compile inside the window fails the run
anyway.  A program without the ledger (the parent) gives ``None``
everywhere, and the result line leaves the metric out.
"""
from typing import List, Optional


def events_before_window(ctx, stages) -> Optional[List[dict]]:
    """The ledger's events of ``stages`` that began before the window, or
    ``None`` where the program keeps no ledger."""
    try:
        from flink_parameter_server_tpu.telemetry import compile_ledger
    except ImportError:
        return None
    cut = min((s["start"] for s in ctx["spans"]), default=float("inf"))
    return [
        e for e in compile_ledger.events()
        if e["stage"] in stages and e["t0"] < cut
    ]


def seconds_before_window(ctx, stages) -> Optional[float]:
    events = events_before_window(ctx, stages)
    if events is None:
        return None
    return sum(e["t1"] - e["t0"] for e in events)


def count_before_window(ctx, stages) -> Optional[float]:
    events = events_before_window(ctx, stages)
    return None if events is None else float(len(events))


def counter_total(name: str) -> Optional[float]:
    """The program's counter ``name`` summed over its label sets, or ``None``
    where nothing registered it (the parent; a program that did no such
    work)."""
    try:
        from flink_parameter_server_tpu.telemetry.registry import get_registry
    except ImportError:
        return None
    entries = get_registry().snapshot().get(name)
    if not entries:
        return None
    return float(sum(e["value"] or 0.0 for e in entries))
