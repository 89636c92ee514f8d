"""Distinct rows a step's rule rewrote over the keys it was pushed (%): what
the push's combine is worth.  From the program's own counters, the gauges
``store_rule_rows`` and ``store_rule_keys`` that ``StreamingDriver`` sets from
the last dispatch's outputs once the loop has ended (no fetch inside the
window).  A program without them (an ``add`` store, the parent) reports
nothing."""


def read(ctx):
    try:
        from flink_parameter_server_tpu.telemetry.registry import get_registry
    except ImportError:
        return None
    gauges = get_registry().snapshot()

    def value(name):
        entries = gauges.get(name) or [{}]
        return entries[0].get("value")

    rows, keys = value("store_rule_rows"), value("store_rule_keys")
    return 100.0 * rows / keys if rows and keys else None
